"""The latent (MLA) layers' decode attention against its memory roofline:
the least time the chip could take to read the latents really attended
(``latent_bytes`` below, from StepStats ``latent_context_sum`` per decode
record of the window), at the published HBM bandwidth, over the device time
of the scope ``attention_latent`` per run of a decode program
(``jit_window``).  The bytes are a token's ``kv_lora_rank +
qk_rope_head_dim`` values in bf16; a page that is stored wider, or read
once as keys and once as values, reads lower."""

SOURCE = "device_trace"
LAYER = "latent attention"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def latent_bytes(positions: float, layers: int, cfg: dict) -> float:
    """Bytes of ``positions`` attended latents in each of ``layers`` latent
    layers, bf16."""
    return (positions * layers
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2)


def read(ctx):
    from benchmarks.chip.layer_metrics import _kda
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    layers = _kda.layers_of(ctx, _kda.LATENT)
    recs = _kda.records(ctx, "decode", "latent_context_sum")
    if not ctx["peaks"] or not layers or not recs:
        return None
    ms = decode_step_ms(ctx, ("attention_latent",))
    if not ms:
        return None
    positions = sum(r["latent_context_sum"] for r in recs) / len(recs)
    least_s = (latent_bytes(positions, layers, _kda.model_of(ctx))
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
