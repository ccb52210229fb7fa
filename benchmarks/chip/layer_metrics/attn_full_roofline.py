"""The decode attention kernel's share of its memory roofline in a table of
which only some layers keep K and V pages: the least time the chip could
take to read the K and V really attended (``kv_bytes_attended`` below: the
``full_attention`` layers of ``layer_types`` at StepStats ``context_sum`` of
the window's decode steps, scaled to the traced interval), at the published
HBM bandwidth, over the paged kernel's device time in the trace.
``attn_decode_roofline`` counts every layer as full and is not given a cell
whose other layers keep a state.  A configuration without ``layer_types``,
or one with a window (``attn_mixed_roofline``'s), gives nothing."""

SOURCE = "device_trace"
LAYER = "attention kernel"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def kv_bytes_attended(context_tokens: float, cfg: dict) -> float:
    """Bytes of K and V a decode pass over ``context_tokens`` attended
    positions must read: each full layer, every KV head, bf16."""
    full = sum(1 for k in cfg["layer_types"] if k == "full_attention")
    return (context_tokens * full * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def read(ctx):
    from benchmarks.chip.layer_metrics._common import op_seconds

    t, cfg = ctx["trace"], ctx["config"]
    kinds = cfg.get("layer_types") or ()
    if (not t or not ctx["peaks"] or "full_attention" not in kinds
            or "sliding_attention" in kinds):
        return None
    kernel_s = op_seconds(t, "paged_attention")
    w0, w1 = ctx["window"]
    tokens = sum(r.get("context_sum", 0) for r in ctx["steps"]
                 if r.get("kind") == "decode")
    if not kernel_s or not tokens:
        return None
    per_s = tokens / (w1 - w0)
    least_s = (kv_bytes_attended(per_s * t["window_s"], cfg) / ctx["chips"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
