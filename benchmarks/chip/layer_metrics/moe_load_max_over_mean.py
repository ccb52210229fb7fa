"""The largest token count on one held expert in a layer of a decode step
(StepStats ``moe_load_max``) over the mean count on a held expert
(``moe_pairs_held`` / (experts held x sparse layers)), averaged over the
window's decode records.  How uneven the router's load is: the grouped
matmul's row tiles are sized by the largest group."""

SOURCE = "program_counter"
LAYER = "expert layer"
UNIT = "x"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._moe import (decode_records,
                                                     held_experts)

    held = held_experts(ctx)
    ratios = [r["moe_load_max"] * held / r["moe_pairs_held"]
              for r in decode_records(ctx) if r.get("moe_pairs_held")]
    if not ratios or not held:
        return None
    return sum(ratios) / len(ratios)
