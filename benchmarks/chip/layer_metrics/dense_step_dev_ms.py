"""Device milliseconds per run of a decode program (``jit_window``) in the
scopes of the weight matmuls: ``qkv_proj``, ``o_proj``, ``mlp``, ``lm_head``
(norms and residuals included, whatever HLO category the ops have).  A time,
not a roofline share: the weights' floor is bytes / 819 GB/s."""

SOURCE = "device_trace"
LAYER = "step programs"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import DENSE, decode_step_ms

    return decode_step_ms(ctx, DENSE)
