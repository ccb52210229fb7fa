"""Median device span of one prefill chunk program (``jit_prefill`` in the
trace's XLA Modules line), whatever its (T, W)."""

SOURCE = "device_trace"
LAYER = "step programs"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import program_median_ms

    return program_median_ms(ctx["trace"], "prefill")
