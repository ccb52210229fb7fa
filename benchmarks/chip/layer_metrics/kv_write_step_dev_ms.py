"""Device milliseconds per run of a decode program (``jit_window``) in the
scope ``kv_write``: the scatter of the step's K and V into the paged cache,
whatever HLO category its ops have.  The whole-cache ``copy`` ops XLA's layout
assignment puts between the scatter's and the kernel's layout carry no
``op_name`` and are not in here: ``cache_copy_share`` and ``scope_coverage``
see them."""

SOURCE = "device_trace"
LAYER = "KV cache layout"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    return decode_step_ms(ctx, ("kv_write",))
