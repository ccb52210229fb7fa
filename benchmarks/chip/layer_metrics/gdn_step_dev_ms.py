"""Device milliseconds per run of a decode program (``jit_window``) in the
scopes of a table's gated-delta-rule layers: ``gdn_proj`` (the five input
matmuls on the raw stream), ``gdn_conv`` (the short convolution, its state,
SiLU, the L2 norms, decay and step size), ``gdn_recurrent`` (the token
recurrence over the seats' states, the kernel ``gdn_step`` and the layout of
its small operands), ``gdn_out`` (the per-head output norm and the SiLU
gate).  A time, not a share; a program without the scopes gives nothing."""

SOURCE = "device_trace"
LAYER = "linear-attention layer"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"

SCOPES = ("gdn_proj", "gdn_conv", "gdn_recurrent", "gdn_chunk", "gdn_out")


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    return decode_step_ms(ctx, SCOPES) or None
