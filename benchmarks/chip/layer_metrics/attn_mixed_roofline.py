"""The decode attention kernel's share of its memory roofline in a model
whose layers differ in what they attend: the least time the chip could take
to read the K and V really attended (``kv_bytes_attended`` below: full
layers at StepStats ``context_sum``, layers with a window at
``context_sum_window``, of the window's decode steps, scaled to the traced
interval), at the published HBM bandwidth, over the kernel's device time in
the trace.  ``attn_decode_roofline`` counts every layer as full and is not
given a cell with a window.  A program without ``context_sum_window`` gives
nothing."""

SOURCE = "device_trace"
LAYER = "attention kernel"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def kv_bytes_attended(full_tokens: float, window_tokens: float,
                      cfg: dict) -> float:
    """Bytes of K and V a decode pass must read: ``full_tokens`` attended
    positions in each full layer, ``window_tokens`` in each layer with a
    window, every KV head, bf16."""
    kinds = cfg["layer_types"]
    n_win = sum(1 for k in kinds if k == "sliding_attention")
    per_pos = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    return per_pos * (full_tokens * (len(kinds) - n_win)
                      + window_tokens * n_win)


def read(ctx):
    from benchmarks.chip.layer_metrics._common import op_seconds

    t = ctx["trace"]
    if not t or not ctx["peaks"] or "layer_types" not in ctx["config"]:
        return None
    kernel_s = op_seconds(t, "paged_attention")
    w0, w1 = ctx["window"]
    dec = [r for r in ctx["steps"] if r.get("kind") == "decode"]
    full = sum(r.get("context_sum", 0) for r in dec)
    win = sum(r.get("context_sum_window", 0) for r in dec)
    if not kernel_s or not full or not win:
        return None
    # attended positions per second of the window, over the traced interval
    scale = t["window_s"] / (w1 - w0)
    least_s = (kv_bytes_attended(full * scale, win * scale, ctx["config"])
               / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
