"""Real over computed tokens of the prefill programs dispatched in the window
(StepStats ``real_tokens`` / ``padded_tokens`` of the prefill records): what
the scheduler's chunking leaves of each bucket.  PR 38's quantity (0.744 ->
0.953 in longgen), summed by hand until PR 39."""

SOURCE = "program_counter"
LAYER = "scheduler"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def read(ctx):
    pre = [r for r in ctx["steps"] if r.get("kind") == "prefill"]
    padded = sum(r["padded_tokens"] for r in pre)
    if not padded:
        return None
    return 100.0 * sum(r["real_tokens"] for r in pre) / padded
