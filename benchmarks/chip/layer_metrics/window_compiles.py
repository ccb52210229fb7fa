"""Programs compiled between window open and window close: compilewatch compiles_total from the worker /health probe, close minus open. Should read 0."""

SOURCE = "program_counter"
LAYER = "engine loop"
UNIT = "count"
BETTER = "lower"
MOVES = "itl_p95_ms"


def read(ctx):
    return float(ctx["window_compiles"])
