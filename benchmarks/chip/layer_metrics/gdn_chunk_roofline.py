"""The scalar-gate chunked form's share of the chip's bf16 peak in prefill:
the FLOPs the form needs for the chunk's real tokens (``chunk_flops`` below,
the matrix products of ``ops/gated_delta.py: gdn_chunked`` written out), at
the published peak, over the device time of the scope ``gdn_chunk`` per run
of a prefill program (``jit_prefill``), the tokens from StepStats
``real_tokens`` of the window's prefill records.  It reads low: the form
runs float32 at the highest matmul precision (six bf16 passes a product)
over chunks of 64, and a chunk's pad tokens are counted in the time and not
in the work."""

SOURCE = "device_trace"
LAYER = "linear-attention layer"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"

CHUNK = 64


def chunk_flops(tokens: float, layers: int, cfg: dict) -> float:
    """Two a multiply-add, a token a head, with ``C`` the chunk: against
    the chunk's ``C`` columns ``K K^T``, ``Q K^T`` and ``T (K exp G)`` of
    ``dk`` and ``T V``, ``B U`` of ``dv``; against the state ``Wk S``, ``q
    S`` and ``Kbar^T U`` of ``dk x dv``.  The triangular inverse (by
    substitution, ``C^2`` a token at most) is left out."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    a_token = 2 * CHUNK * (3 * dk + 2 * dv) + 3 * 2 * dk * dv
    return tokens * layers * cfg["linear_num_value_heads"] * a_token


def read(ctx):
    from benchmarks.chip.layer_metrics import _kda
    from benchmarks.chip.layer_metrics._scopes import summary
    from benchmarks.chip.scopes import program_scope_ms

    model = _kda.model_of(ctx)
    layers = _kda.layers_of(ctx, _kda.LINEAR)
    recs = _kda.records(ctx, "prefill", "state_rows")
    if (not ctx["peaks"] or not layers or not recs
            or "linear_value_head_dim" not in model):
        return None
    ms = program_scope_ms(summary(ctx), "prefill", ("gdn_chunk",))
    if not ms:
        return None
    tokens = sum(r["real_tokens"] for r in recs) / len(recs)
    least_s = (chunk_flops(tokens, layers, model)
               / ctx["peaks"]["bf16_flops"])
    return 100.0 * least_s / (ms / 1e3)
