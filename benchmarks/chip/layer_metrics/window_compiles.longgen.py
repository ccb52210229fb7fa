"""``window_compiles`` in the cell whose streams' pace is its end-to-end
metric: a program compiled inside longgen's window stops all 128 streams
for as long as it takes.  Should read 0: an engine with seat state compiles
its step programs when it is built."""

SOURCE = "program_counter"
LAYER = "engine loop"
UNIT = "count"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import sibling_read

    return sibling_read("window_compiles", ctx)
