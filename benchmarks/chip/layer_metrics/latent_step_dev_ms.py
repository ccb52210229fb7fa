"""Device milliseconds per run of a decode program (``jit_window``) in the
scope ``attention_latent``: the absorbed queries' product with ``Wuk``, the
Pallas walk over the latent pages of every latent (MLA) layer, and the
result's product with ``Wuv`` (PR 41).  The denominator of
``latent_attn_roofline`` as a time of its own: where every layer is latent
it is the step's whole attention.  A time, not a share; a program without
the scope gives nothing."""

SOURCE = "device_trace"
LAYER = "latent attention"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    return decode_step_ms(ctx, ("attention_latent",)) or None
