"""Median time to first token from the due time, over requests whose first
token arrived in the window (client clock) — the open-loop cell's TTFT.  Not
an end-to-end metric there: with 100 requests a window and arrivals that fall
anywhere in an 84 ms decode step, its runs spread 3.5-4.4% of the median
(PR 23), more than half of the largest bound allowed.  What it should move is
``itl_p95_ms``: both are set by prefill chunks interleaved with decode
steps."""

SOURCE = "host_clock"
LAYER = "client view"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p95_ms"


def read(ctx):
    return ctx["client"]["ttft_p50_ms"]
