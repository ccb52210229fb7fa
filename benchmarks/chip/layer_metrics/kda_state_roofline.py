"""The token recurrence's share of its memory roofline in decode: the least
time the chip could take to read every live seat's state once and write it
once (``state_bytes`` below, from StepStats ``state_rows`` per decode
record of the window), at the published HBM bandwidth, over the device time
of the scope ``kda_recurrent`` per run of a decode program (``jit_window``).
Bound by bytes: a step does 7 FLOPs a state value.  The bytes are those of
the algorithm, whatever implements it: a form that passes over the state
three times reads a third."""

SOURCE = "device_trace"
LAYER = "linear-attention layer"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def state_bytes(rows: float, layers: int, cfg: dict) -> float:
    """Bytes of ``rows`` seats' float32 states ``[heads, hd, hd]`` in each
    of ``layers`` linear-attention layers, read once and written once."""
    return (rows * layers * 2 * cfg["num_attention_heads"]
            * cfg["head_dim"] ** 2 * 4)


def read(ctx):
    from benchmarks.chip.layer_metrics import _kda
    from benchmarks.chip.layer_metrics._scopes import decode_step_ms

    layers = _kda.layers_of(ctx, _kda.LINEAR)
    recs = _kda.records(ctx, "decode", "state_rows")
    if not ctx["peaks"] or not layers or not recs:
        return None
    ms = decode_step_ms(ctx, ("kda_recurrent",))
    if not ms:
        return None
    rows = sum(r["state_rows"] for r in recs) / len(recs)
    least_s = (state_bytes(rows, layers, _kda.model_of(ctx))
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
