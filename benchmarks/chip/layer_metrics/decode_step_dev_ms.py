"""Median device span of one decode step program (``jit_window`` in the
trace's XLA Modules line)."""

SOURCE = "device_trace"
LAYER = "step programs"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import program_median_ms

    return program_median_ms(ctx["trace"], "window")
