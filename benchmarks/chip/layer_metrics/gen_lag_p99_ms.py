"""How late the load generator sent: 99th percentile of send time minus due time over requests sent in the window (client clock)."""

SOURCE = "host_clock"
LAYER = "load generator"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p95_ms"


def read(ctx):
    return ctx["client"]["gen_lag_p99_ms"]
