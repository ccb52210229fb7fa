"""Share of the window the worker's event loop was not free: the sum of
``loop_busy_s`` over the step records dispatched in the window (StepStats
JSONL; seconds the loop spent outside a blocking select between two
handoffs, from ``runtime.loop_busy``) over the window's seconds.  Three things
are in it: the engine-loop task (``engine_loop_busy_share``, ``host_s``), every
stream's way out (queue, pack, write, drain per token) with the runtime's own
tasks, and the loop thread's waits for the interpreter lock while the dispatch
or the fetch thread holds it: the loop cannot run then either, so it counts
against the loop's room, but it is not callback work and a cheaper frame does
not shrink it.  The records' ``loop_cpu_s`` (the loop thread's CPU seconds over
the same stretches) is the part that was work: divide by that, not by this,
for a cost per token.  Near 100% the loop, not the device, paces the tokens.
None where the records carry no ``loop_busy_s`` (a parent of PR 39)."""

SOURCE = "program_counter"
LAYER = "worker event loop"
UNIT = "%"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    w0, w1 = ctx["window"]
    busy = [r["loop_busy_s"] for r in ctx["steps"] if "loop_busy_s" in r]
    if not busy:
        return None
    return 100.0 * sum(busy) / (w1 - w0)
