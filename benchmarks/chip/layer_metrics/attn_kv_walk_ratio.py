"""How far the decode attention kernel's KV walk is from the tokens it
attends: KV pages the window's decode launches visited in a layer (StepStats
``kv_blocks_walked``, counted by the engine from each row's context and the
tile the decode window was traced with) times the block size, over the
positions those launches attended (``context_sum``).  1.0 is a walk that
reads exactly what it attends; a kernel whose grid walks every column of the
block table reads ``rows x table width`` pages whatever the contexts.  A
program without the counter gives nothing."""

SOURCE = "program_counter"
LAYER = "attention kernel"
UNIT = "x"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    steps = [r for r in ctx["steps"]
             if r.get("kind") == "decode" and "kv_blocks_walked" in r]
    walked = sum(r["kv_blocks_walked"] for r in steps)
    attended = sum(r.get("context_sum", 0) for r in steps)
    if not walked or not attended:
        return None
    return walked * ctx["engine"]["block_size"] / attended
