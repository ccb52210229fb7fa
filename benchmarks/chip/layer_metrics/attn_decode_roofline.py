"""The decode attention kernel's share of its memory roofline: the least
time the chip could take to read the K and V it really attends
(``kv_bytes_attended`` below, from StepStats ``context_sum`` of the window's
decode steps, scaled to the traced interval), at the published HBM
bandwidth, over the kernel's device time in the trace.  Bound by bytes, not
FLOPs: decode attention does 2 FLOPs per KV byte read."""

SOURCE = "device_trace"
LAYER = "attention kernel"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def kv_bytes_attended(context_tokens: int, cfg: dict) -> float:
    """Bytes of K and V a decode pass over ``context_tokens`` attended
    positions must read: every layer, every KV head, bf16."""
    return (context_tokens * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * 2 * cfg["num_hidden_layers"])


def read(ctx):
    from benchmarks.chip.layer_metrics._common import op_seconds

    t = ctx["trace"]
    if not t or not ctx["peaks"]:
        return None
    kernel_s = op_seconds(t, "paged_attention")
    w0, w1 = ctx["window"]
    tokens = sum(r.get("context_sum", 0) for r in ctx["steps"]
                 if r.get("kind") == "decode")
    if not kernel_s or not tokens:
        return None
    # attended tokens per second of the window, over the traced interval;
    # each chip reads its share of the KV heads
    per_s = tokens / (w1 - w0)
    bytes_traced = kv_bytes_attended(per_s * t["window_s"], ctx["config"])
    least_s = bytes_traced / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
