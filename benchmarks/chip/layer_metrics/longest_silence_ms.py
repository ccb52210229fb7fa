"""The longest stretch of the window in which no stream got a token (client
clock): a stall of the whole system, which no median and no 95th percentile
of gaps shows and a closed loop's ``out_tok_s`` pays in full."""

SOURCE = "host_clock"
LAYER = "client view"
UNIT = "ms"
BETTER = "lower"
MOVES = "out_tok_s"


def read(ctx):
    return ctx["client"]["longest_silence_ms"]
