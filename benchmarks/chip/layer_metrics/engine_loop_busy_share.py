"""Share of the window the engine-loop task was busy: the sum of ``host_s`` over
the step records dispatched in the window (StepStats JSONL; ``host_s`` is the
loop's time between two handoffs to the dispatch thread less its waits for a
landing, for work and for that thread) over the window's seconds.  What the
loop costs while the device hides it: the floor ``tpot_p50_ms`` meets when the
step programs get faster."""

SOURCE = "program_counter"
LAYER = "engine loop"
UNIT = "%"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    w0, w1 = ctx["window"]
    host = [r["host_s"] for r in ctx["steps"] if "host_s" in r]
    if not host:
        return None       # a program whose records carry no host seconds
    return 100.0 * sum(host) / (w1 - w0)
