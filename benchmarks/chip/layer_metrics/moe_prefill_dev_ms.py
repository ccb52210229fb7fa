"""Device milliseconds per run of a prefill program (``jit_prefill``) in the
scope ``moe_experts`` of a table's sparse layers: the dispatch, the three
grouped matmuls over the experts held and the combine, at the chunk's rows
(a T=512 chunk: 5120 pairs in codegen, 4096 in longgen) where
``moe_step_dev_ms`` reads the same scope at a decode window's.  A mean over
the capture's prefill programs, whatever their T; a time, not a share.  A
program without the scope, or a capture without a prefill, gives nothing."""

SOURCE = "device_trace"
LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"

SCOPES = ("moe_experts",)


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import summary
    from benchmarks.chip.scopes import program_scope_ms

    return program_scope_ms(summary(ctx), "prefill", SCOPES) or None
