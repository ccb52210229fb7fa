"""Device milliseconds per run of a prefill program (``jit_prefill``) in the
scope ``attention_latent`` of a table's latent (MLA) layers: the gather of
the chunk's table and the chunk's attention over it, every latent layer of
the program (8 in ``longcat-flash-omni-ep32``, 1 in ``ling-3.0-flash-ep8``),
where ``latent_step_dev_ms`` reads the same scope at a decode window's.
Since PR 54 that attention is a Pallas kernel (``latent_chunk_attention``)
that walks the keys in tiles as far as the chunk's context; before, an
einsum over the table's whole width.  The kernel is one custom call a
layer, no ``while``: nothing here is a loop's own span counted beside its
body (the program's ``while`` is the expert layer's slab loop, under no
scope).  A mean over the capture's prefill programs, whatever their T; a
time, not a share.  A program without the scope, or a capture without a
prefill, gives nothing."""

SOURCE = "device_trace"
LAYER = "latent attention"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"

SCOPES = ("attention_latent",)


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import summary
    from benchmarks.chip.scopes import program_scope_ms

    return program_scope_ms(summary(ctx), "prefill", SCOPES) or None
