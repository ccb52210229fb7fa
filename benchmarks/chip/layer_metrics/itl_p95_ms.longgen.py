"""``itl_p95_ms`` in the cell that runs 128 streams against one frontend and
a one-thread load generator: the 95th percentile of raw gaps between
consecutive SSE token events ending in the window (client clock).  Not an
end-to-end metric there: a gap in that tail is a decode step plus several
prefill chunks plus what 6-7 k events a second queue up on the host, it moves
by whole chunks, and its runs spread 3-5% of the median (PR 34), twice the
whole bound.  What it should move is ``tpot_p50_ms``: both rise with the
chunks that run between decode steps."""

SOURCE = "host_clock"
LAYER = "client view"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    return ctx["client"]["itl_p95_ms"]
