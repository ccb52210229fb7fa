"""``sent_gap_p95_ms`` in the cell whose gap tail is too wide to be end-to-end
(``itl_p95_ms.longgen``): the gap at the worker's socket, 128 streams' frames
through one event loop.  What it should move there is ``tpot_p50_ms``."""

SOURCE = "program_span"
LAYER = "worker stream-out"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import sibling_read

    return sibling_read("sent_gap_p95_ms", ctx)
