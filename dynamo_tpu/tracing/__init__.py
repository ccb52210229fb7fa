"""Distributed request tracing: timed spans over the existing
``traceparent`` propagation, per-stage latency attribution, trace export.

The span model lives in :mod:`.span`, the process-global collector (ring
buffer + sampling + slow-request auto-dump) in :mod:`.collector`, the
JSONL / in-memory / Prometheus sinks in :mod:`.export`, and the offline
per-trace assembler (also a CLI: ``python -m dynamo_tpu.tracing``) in
:mod:`.assemble`, the per-stream gap histogram a span can carry in
:mod:`.hist`.

Stage names instrumented across the serving path::

    frontend.request      root span of one HTTP request
    frontend.admission    admission-controller queue wait
    frontend.tokenize     template render + tokenization
    migration.attempt     one issue of the request to the cluster
    migration.backoff     retry backoff sleep
    router.select         KV-router score + select
    transport.send        client push → first response frame
    worker.ingress        worker-side root: request arrival → stream done
    worker.queue          engine admission → first scheduled chunk
    engine.prefill        first scheduled chunk → first token
    engine.decode         first token → stream end

The road between the socket and the engine rides those spans as attrs and
events, stamped as floats and attached once, when the span ends. The way in
is stamped once a request; the way out per token, and only for a trace an
exporter may take (``SpanCollector.keeps``: head-sampled, or a slow
threshold is set)::

    worker.ingress    attrs upstream_s (frontend.request start → here) and
                      wire_s (transport client's write → here), absent when
                      the caller stamped neither; event first_sent, attrs
                      frames, send_sum_s, send_max_s and sent_gaps (a
                      :class:`.hist.GapHistogram` of the gaps between this
                      stream's send completions)
    engine.decode     attrs wake_sum_s, wake_max_s (fetch landed → the
                      stream's task holds the token)

(Ingress start → ``worker.queue`` start is the payload's unpack and the
request's build.) ``python -m dynamo_tpu.tracing spans.jsonl --summary``
reads them all (:func:`.assemble.road_summary`). ``worker.stream_out`` is
not a span but a profiler annotation (``engine.PHASES``) around each data
frame's pack + write, on the same streams. How busy each process's one
event loop is: ``event_loop_busy_seconds_total`` on its ``/metrics`` and,
windowed on a worker, ``engine_event_loop_busy_ratio`` beside
``engine_host_busy_ratio``; a loop above ~0.8 paces its process (split it or
replicate it).
"""

from .collector import (
    SpanCollector, configure, get_tracer, reset, trace_span,
)
from .export import InMemorySpanExporter, JsonlSpanExporter
from .span import Span

__all__ = [
    "InMemorySpanExporter",
    "JsonlSpanExporter",
    "Span",
    "SpanCollector",
    "configure",
    "get_tracer",
    "reset",
    "trace_span",
]
