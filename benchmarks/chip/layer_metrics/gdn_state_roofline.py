"""The gated delta rule's token recurrence as a share of its memory roofline
in decode: the least time the chip could take to read every live seat's
state once and write it once (``state_bytes`` below, from StepStats
``state_rows`` per decode record of the window), at the published HBM
bandwidth, over the device time of the scope ``gdn_recurrent`` per run of a
decode program (``jit_window``).  Bound by bytes: a step does 7 FLOPs a
state value.  The bytes are the logical ones, ``heads x dk x dv`` float32,
whatever the pool's layout pads and whatever implements the step."""

SOURCE = "device_trace"
LAYER = "linear-attention layer"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def state_bytes(rows: float, layers: int, cfg: dict) -> float:
    """Bytes of ``rows`` seats' float32 states ``[heads, dk, dv]`` in each
    of ``layers`` gated-delta-rule layers, read once and written once."""
    return (rows * layers * 2 * cfg["linear_num_value_heads"]
            * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] * 4)


def read(ctx):
    from benchmarks.chip.layer_metrics import _kda
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    model = _kda.model_of(ctx)
    layers = _kda.layers_of(ctx, _kda.LINEAR)
    recs = _kda.records(ctx, "decode", "state_rows")
    if (not ctx["peaks"] or not layers or not recs
            or "linear_value_head_dim" not in model):
        return None
    ms = decode_step_ms(ctx, ("gdn_recurrent",))
    if not ms:
        return None
    rows = sum(r["state_rows"] for r in recs) / len(recs)
    least_s = (state_bytes(rows, layers, model)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
