"""Median way in of the requests whose first frame left the worker inside the
window: ``upstream_s`` of the ``worker.ingress`` span (the frontend's
``frontend.request`` start to the ingress span's start: HTTP parse and
validation, admission, tokenization, routing, the wire, the worker's read of
the frame; wall clocks, one host) plus the stretch from the ingress span's
start to the start of the same trace's ``worker.queue`` (payload unpack and
request build; both the worker's ``time.monotonic()``).  With
``queue_wait_p50_ms``, ``prefill_span_p50_ms`` and ``first_emit_lag_p50_ms`` it
leaves of the client's first-token time only the frontend's way out.  None
where the program stamps no ``upstream_s`` (a parent of PR 39)."""

SOURCE = "program_span"
LAYER = "frontend + transport (way in)"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.metrics import percentile

    w0, w1 = ctx["window"]
    queued = {s["trace_id"]: s["start_mono"] for s in ctx["spans"]
              if s.get("name") == "worker.queue"}
    ms = []
    for s in ctx["spans"]:
        if s.get("name") != "worker.ingress":
            continue
        up = (s.get("attrs") or {}).get("upstream_s")
        at = {e.get("name"): e.get("offset_s") for e in s.get("events") or []}
        if (up is None or "first_sent" not in at
                or s.get("trace_id") not in queued):
            continue
        if w0 <= s["start_mono"] + at["first_sent"] < w1:
            ms.append((up + queued[s["trace_id"]] - s["start_mono"]) * 1e3)
    return percentile(ms, 50)
