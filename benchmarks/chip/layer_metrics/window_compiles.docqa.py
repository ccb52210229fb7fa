"""``window_compiles`` in the cell whose end-to-end tail is TTFT: a program
compiled inside docqa's window stalls every first token behind it."""

SOURCE = "program_counter"
LAYER = "engine loop"
UNIT = "count"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import sibling_read

    return sibling_read("window_compiles", ctx)
