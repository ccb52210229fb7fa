"""(token, expert) pairs whose choice is a zero-compute (identity) expert
over all pairs of the window's decode steps (StepStats ``moe_pairs_zero`` /
``moe_pairs``; PR 41).  Such a pair adds ``w * x`` where the token lives and
reads no expert's weights, on any chip: the share of a step's routing that
costs neither bytes nor an exchange.  With 256 of 768 router outputs
zero-compute and an unbiased draw it reads about a third.  A program
without the counter (the parent commit) gives nothing."""

SOURCE = "program_counter"
LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._moe import decode_records

    recs = [r for r in decode_records(ctx) if "moe_pairs_zero" in r]
    pairs = sum(r["moe_pairs"] for r in recs)
    if not pairs:
        return None
    return 100.0 * sum(r["moe_pairs_zero"] for r in recs) / pairs
