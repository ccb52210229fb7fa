"""Device milliseconds per run of a decode program (``jit_window``) in the
scopes of a table's sparse layers: ``moe_router`` (norm, router matmul,
softmax, top-k, sort), ``moe_experts`` (dispatch, the grouped matmuls over
the experts held, combine) and ``moe_shared`` (the shared expert and the
residual).  A time, not a share; a program without the scopes gives
nothing."""

SOURCE = "device_trace"
LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"

SCOPES = ("moe_router", "moe_experts", "moe_shared")


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    return decode_step_ms(ctx, SCOPES) or None
