"""How far the decode kernel's KV walk of a layer with a window is from the
keys it attends: pages the window's decode launches visited in ONE such
layer (StepStats ``kv_blocks_walked_window``, counted by the engine from
each row's context, the window and the tile the decode window was traced
with) times the block size, over ``min(context, window)`` summed over the
same rows (``context_sum_window``).  A walk that starts at the window's
tile reads a little over 1; one that starts at page 0 reads context /
window.  A program without the counters gives nothing."""

SOURCE = "program_counter"
LAYER = "attention kernel"
UNIT = "x"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    steps = [r for r in ctx["steps"] if r.get("kind") == "decode"]
    walked = sum(r.get("kv_blocks_walked_window", 0) for r in steps)
    attended = sum(r.get("context_sum_window", 0) for r in steps)
    if not walked or not attended:
        return None
    return walked * ctx["engine"]["block_size"] / attended
