"""95th percentile of time to first token, from the due time.  In the open-loop cell, where ``ttft_p50_ms`` is not an end-to-end
metric (its runs spread 3.5-4.4% of the median, PR 23): what it should move
there is ``itl_p95_ms`` — the gap tail in that cell is one decode step plus
one prefill chunk interleaved with it."""

SOURCE = "host_clock"
LAYER = "client view"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p95_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import sibling_read

    return sibling_read("ttft_p95_ms", ctx)
