"""Live rows over computed rows of the decode steps dispatched in the window
(StepStats counts ``live_rows`` / ``rows``; its times are not read)."""

SOURCE = "program_counter"
LAYER = "scheduler"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"


def read(ctx):
    dec = [r for r in ctx["steps"] if r.get("kind") == "decode"]
    rows = sum(r["rows"] for r in dec)
    if not rows:
        return None
    return 100.0 * sum(r["live_rows"] for r in dec) / rows
