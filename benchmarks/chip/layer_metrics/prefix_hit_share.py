"""Share of the window's prompt tokens that were not prefilled because their
blocks were found in the prefix cache: 1 - (tokens the prefill steps of the
window really computed, StepStats ``real_tokens``) / (prompt tokens of the
requests whose first token arrived in the window).  Counts only.  Must read
about 0 where nothing is shared."""

SOURCE = "program_counter"
LAYER = "prefix cache"
UNIT = "%"
BETTER = "higher"
MOVES = "ttft_p50_ms"


def read(ctx):
    w0, w1 = ctx["window"]
    asked = 0
    for r in ctx["records"]:
        ev = r.get("events") or []
        if ev and w0 <= ev[0][0] < w1:
            asked += r["prompt_tokens_sent"]
    done = sum(r["real_tokens"] for r in ctx["steps"]
               if r.get("kind") == "prefill")
    if not asked:
        return None
    return 100.0 * (1.0 - done / asked)
