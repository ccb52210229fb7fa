"""Device milliseconds per run of a decode program (``jit_window``) in the
scopes of a table's linear-attention (KDA) layers: ``kda_proj`` (norm and
the five input matmuls), ``kda_conv`` (the short convolution, its state,
SiLU, the L2 norms, the decay), ``kda_recurrent`` (the token recurrence
over the seats' states), ``kda_out`` (the per-head output norm).  A time,
not a share; a program without the scopes gives nothing."""

SOURCE = "device_trace"
LAYER = "linear-attention layer"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"

SCOPES = ("kda_proj", "kda_conv", "kda_recurrent", "kda_chunk", "kda_out")


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    return decode_step_ms(ctx, SCOPES) or None
