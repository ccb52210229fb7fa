"""Share of the device's busy time in the capture whose op carries a stage
name of the program (``dynamo_tpu.engine.model.SCOPES``) in its ``op_name``.
The rest are ops the compiler makes itself and names after nothing."""

SOURCE = "device_trace"
LAYER = "step programs"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._scopes import summary

    s = summary(ctx)
    if not s or s.get("coverage") is None:
        return None
    return 100.0 * s["coverage"]
