"""(token, expert) pairs whose expert is held here over all pairs of the
window's decode steps (StepStats ``moe_pairs_held`` / ``moe_pairs``).  A
guard that routing runs over every routed expert and not over the ones
held: half the experts held reads about 50."""

SOURCE = "program_counter"
LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._moe import decode_records

    recs = decode_records(ctx)
    pairs = sum(r["moe_pairs"] for r in recs)
    if not pairs:
        return None
    return 100.0 * sum(r["moe_pairs_held"] for r in recs) / pairs
