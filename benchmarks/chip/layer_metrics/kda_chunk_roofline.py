"""The chunked form's share of the chip's bf16 peak in prefill: the FLOPs
the token recurrence itself needs for the chunk's real tokens
(``recurrence_flops`` below: 7 a state value a token, whatever the chunk
size and whatever the chunked form spends on its triangular solve), at the
published peak, over the device time of the scope ``kda_chunk`` per run of
a prefill program (``jit_prefill``), the tokens from StepStats
``real_tokens`` of the window's prefill records.  It reads low: the form
runs float32 at the highest matmul precision over chunks of 64, and it
reads the same work for any implementation."""

SOURCE = "device_trace"
LAYER = "linear-attention layer"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def recurrence_flops(tokens: float, layers: int, cfg: dict) -> float:
    """Decay, the delta's product and sum, the rank-one update and the
    output's product and sum: 7 operations a value of a head's ``[hd, hd]``
    state a token a layer."""
    return (tokens * layers * cfg["num_attention_heads"] * 7
            * cfg["head_dim"] ** 2)


def read(ctx):
    from benchmarks.chip.layer_metrics import _kda
    from benchmarks.chip.layer_metrics._scopes import summary
    from benchmarks.chip.scopes import program_scope_ms

    layers = _kda.layers_of(ctx, _kda.LINEAR)
    recs = _kda.records(ctx, "prefill", "state_rows")
    if not ctx["peaks"] or not layers or not recs:
        return None
    ms = program_scope_ms(summary(ctx), "prefill", ("kda_chunk",))
    if not ms:
        return None
    tokens = sum(r["real_tokens"] for r in recs) / len(recs)
    least_s = (recurrence_flops(tokens, layers, _kda.model_of(ctx))
               / ctx["peaks"]["bf16_flops"])
    return 100.0 * least_s / (ms / 1e3)
