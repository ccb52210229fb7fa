"""99th percentile of raw gaps between consecutive SSE token events in the window (client clock)."""

SOURCE = "host_clock"
LAYER = "client view"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p95_ms"


def read(ctx):
    return ctx["client"]["itl_p99_ms"]
