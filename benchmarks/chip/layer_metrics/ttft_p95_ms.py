"""95th percentile of time to first token over requests whose first token arrived in the window (client clock)."""

SOURCE = "host_clock"
LAYER = "client view"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    return ctx["client"]["ttft_p95_ms"]
