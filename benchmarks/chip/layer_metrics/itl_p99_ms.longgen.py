"""``itl_p99_ms`` in the cell whose ``itl_p95_ms`` is a per-layer metric
(``itl_p95_ms.longgen``): the same percentile of the same gaps."""

SOURCE = "host_clock"
LAYER = "client view"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import sibling_read

    return sibling_read("itl_p99_ms", ctx)
