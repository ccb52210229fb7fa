"""Median of ``first_sent`` on ``worker.ingress`` minus the end of the same
trace's ``engine.prefill`` (both the worker's ``time.monotonic()``), over the
requests whose first frame left inside the window: from the engine's emit of
the first token to its frame handed to the socket, that is the stream's queue,
the loop's backlog before the stream's task runs, pack, the connection's write
lock, write and drain.  None where the program stamps no ``first_sent``."""

SOURCE = "program_span"
LAYER = "worker stream-out"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.metrics import percentile

    w0, w1 = ctx["window"]
    emitted = {s["trace_id"]: s["end_mono"] for s in ctx["spans"]
               if s.get("name") == "engine.prefill"
               and s.get("end_mono") is not None}
    ms = []
    for s in ctx["spans"]:
        if s.get("name") != "worker.ingress":
            continue
        at = {e.get("name"): e.get("offset_s") for e in s.get("events") or []}
        if "first_sent" not in at or s.get("trace_id") not in emitted:
            continue
        t = s["start_mono"] + at["first_sent"]
        if w0 <= t < w1:
            ms.append((t - emitted[s["trace_id"]]) * 1e3)
    return percentile(ms, 50)
