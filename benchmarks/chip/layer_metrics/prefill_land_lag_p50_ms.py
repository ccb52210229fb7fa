"""Median of ``landed - dispatched`` on the ``engine.prefill`` spans that ended
inside the window: from the enqueue of the prompt-completing chunk to its
sample on the fetch thread, that is the wait on the device behind the decode
window in flight, the prefill program itself and the copy to the host."""

SOURCE = "program_span"
LAYER = "engine loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.metrics import percentile

    w0, w1 = ctx["window"]
    ms = []
    for s in ctx["spans"]:
        if s.get("name") != "engine.prefill":
            continue
        end = s.get("end_mono")
        at = {e.get("name"): e.get("offset_s") for e in s.get("events") or []}
        if (end is None or not w0 <= end < w1
                or "dispatched" not in at or "landed" not in at):
            continue
        ms.append((at["landed"] - at["dispatched"]) * 1e3)
    return percentile(ms, 50) if ms else None
