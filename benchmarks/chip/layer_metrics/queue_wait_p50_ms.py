"""Median of the ``worker.queue`` spans (engine admission to first scheduled
chunk, host clock of the worker) that ended inside the window."""

SOURCE = "program_span"
LAYER = "scheduler"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.metrics import percentile

    w0, w1 = ctx["window"]
    waits = []
    for s in ctx["spans"]:
        if s.get("name") != "worker.queue":
            continue
        end = s.get("end_mono")
        if end is None or not w0 <= end < w1:
            continue
        waits.append((end - s["start_mono"]) * 1e3)
    return percentile(waits, 50)
