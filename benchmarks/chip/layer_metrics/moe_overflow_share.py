"""Held (token, expert) pairs that lay behind the first slab of their sparse
call over all held pairs of the window's decode steps (StepStats
``moe_pairs_overflow`` / ``moe_pairs_held``; PR 43).  A call whose rows
follow the held pairs is shaped for a slab of them
(``dynamo_tpu.parallel.moe.held_rows``) and takes one more pass a slab
beyond: the share says how often the room did not suffice, and what it
counts cost a pass, never a pair.  A program whose calls' rows are all
their pairs reads 0; one without the counter (the parent commit) gives
nothing."""

SOURCE = "program_counter"
LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._moe import decode_records

    recs = [r for r in decode_records(ctx) if "moe_pairs_overflow" in r]
    if not recs:
        return None
    held = sum(r["moe_pairs_held"] for r in recs)
    return 100.0 * sum(r["moe_pairs_overflow"] for r in recs) / max(held, 1)
