"""Median ``engine.prefill`` span (first scheduled chunk to first token emitted,
the worker's clock) over the spans that ended inside the window: the worker's
part of a first token after the queue."""

SOURCE = "program_span"
LAYER = "engine loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.metrics import percentile

    w0, w1 = ctx["window"]
    ms = [(s["end_mono"] - s["start_mono"]) * 1e3 for s in ctx["spans"]
          if s.get("name") == "engine.prefill"
          and s.get("end_mono") is not None and w0 <= s["end_mono"] < w1]
    return percentile(ms, 50) if ms else None
