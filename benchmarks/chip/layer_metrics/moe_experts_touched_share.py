"""Held experts that got at least one token in a decode step, over the
experts held in the model's sparse layers (StepStats
``moe_experts_touched`` per decode record; the configuration's
``num_experts`` and ``mlp_layer_types``).  What a step reads of the expert
weights."""

SOURCE = "program_counter"
LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._moe import (decode_records,
                                                     held_experts)

    recs = decode_records(ctx)
    held = held_experts(ctx)
    if not recs or not held:
        return None
    return (100.0 * sum(r["moe_experts_touched"] for r in recs)
            / (held * len(recs)))
