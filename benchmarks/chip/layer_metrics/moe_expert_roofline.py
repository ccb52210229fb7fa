"""The routed experts' share of their memory roofline in decode: the least
time the chip could take to read the weights of the held experts that got a
token (``expert_bytes`` below, from StepStats ``moe_experts_touched`` per
decode record of the window), at the published HBM bandwidth, over the
device time of the scope ``moe_experts`` per run of a decode program
(``jit_window``).  Bound by bytes: at 32 rows an expert sees one or two
tokens, 2 FLOPs a weight byte a token."""

SOURCE = "device_trace"
LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def expert_bytes(touched: float, cfg: dict) -> float:
    """Bytes of the gate, up and down matrices of ``touched`` experts, in
    bf16."""
    return (touched * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * 2)


def read(ctx):
    from benchmarks.chip.layer_metrics._moe import decode_records
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    if not ctx["peaks"] or "moe_intermediate_size" not in ctx["config"]:
        return None
    recs = decode_records(ctx)
    ms = decode_step_ms(ctx, ("moe_experts",))
    if not recs or not ms:
        return None
    touched = sum(r["moe_experts_touched"] for r in recs) / len(recs)
    least_s = expert_bytes(touched, ctx["config"]) / ctx["peaks"][
        "hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
