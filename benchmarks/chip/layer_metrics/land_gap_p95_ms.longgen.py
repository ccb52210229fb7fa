"""``land_gap_p95_ms`` in the cell whose gap tail is too wide to be end-to-end
(``itl_p95_ms.longgen``): the gap at the landing, a decode step plus the
prefill chunks between two.  What it should move there is ``tpot_p50_ms``."""

SOURCE = "program_counter"
LAYER = "engine loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import sibling_read

    return sibling_read("land_gap_p95_ms", ctx)
