"""Distributed request tracing: span model, collector sinks, sampling,
slow-dump, cross-stage parenting through migration, and the full
frontend → router → worker assembly with a mid-stream crash."""

import asyncio
import json
import time

import aiohttp
import pytest

from dynamo_tpu import tracing
from dynamo_tpu.llm.migration import Migration
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import AsyncEngine
from dynamo_tpu.runtime.transport import ERR_UNAVAILABLE, EngineError
from dynamo_tpu.tracing import InMemorySpanExporter, SpanCollector
from dynamo_tpu.tracing.assemble import (
    assemble_trace, group_traces, load_spans, render_trace,
)
from dynamo_tpu.utils.logging import TraceContext
from dynamo_tpu.utils.metrics import MetricsRegistry

pytestmark = [pytest.mark.anyio, pytest.mark.tracing]


@pytest.fixture
def anyio_backend():
    return "asyncio"


@pytest.fixture
def tracer():
    """Isolated process-global collector, restored after the test."""
    collector = tracing.reset()
    yield collector
    tracing.reset()


# ------------------------- traceparent parsing ---------------------------


def test_traceparent_round_trip():
    tc = TraceContext.new()
    parsed = TraceContext.parse(tc.traceparent())
    assert parsed is not None
    assert parsed.trace_id == tc.trace_id
    assert parsed.span_id == tc.span_id
    assert parsed.flags == tc.flags


def test_traceparent_rejects_version_ff():
    tc = TraceContext.new()
    bad = f"ff-{tc.trace_id}-{tc.span_id}-01"
    assert TraceContext.parse(bad) is None
    # any other version value parses (spec: unknown versions are forward-
    # compatible as long as the tail matches)
    ok = f"01-{tc.trace_id}-{tc.span_id}-01"
    assert TraceContext.parse(ok) is not None


@pytest.mark.parametrize("bad", [
    "",
    "not-a-traceparent",
    "00-short-beef-01",
    "00-" + "0" * 32 + "-" + "ab" * 8 + "-01",   # all-zero trace id
    "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # all-zero span id
    "00-" + "gg" * 16 + "-" + "ab" * 8 + "-01",  # non-hex
    "00_" + "ab" * 16 + "_" + "ab" * 8 + "_01",  # wrong separators
    "00-" + "ab" * 16 + "-" + "ab" * 8,          # missing flags
])
def test_traceparent_rejects_malformed(bad):
    assert TraceContext.parse(bad) is None


# ------------------------- sampling determinism --------------------------


def test_sampling_deterministic_across_collectors():
    """Two collectors make identical keep/drop decisions for every trace
    id — the cluster-wide coordination-free property."""
    a = SpanCollector(sample_ratio=0.5)
    b = SpanCollector(sample_ratio=0.5)
    ids = [f"{i:032x}" for i in range(1, 401)]
    decisions = [a.sampled(t) for t in ids]
    assert decisions == [b.sampled(t) for t in ids]
    # the hash actually splits the population near the ratio
    kept = sum(decisions)
    assert 120 < kept < 280


def test_sampling_edges():
    c = SpanCollector(sample_ratio=0.0)
    assert not c.sampled("ab" * 16)
    c.configure(sample_ratio=1.0)
    assert c.sampled("ab" * 16)


# --------------------------- collector sinks -----------------------------


def test_metrics_observed_even_when_unsampled(tracer):
    reg = MetricsRegistry(prefix="trc_m")
    tracer.attach_metrics(reg)
    tracer.configure(sample_ratio=0.0)
    exp = InMemorySpanExporter()
    tracer.add_exporter(exp)
    span = tracer.start_span("frontend.tokenize")
    span.end()
    body = reg.render().decode()
    assert 'trc_m_stage_latency_seconds_count{stage="frontend.tokenize"}' \
        in body
    # exporters stayed silent: not sampled, not slow
    assert exp.spans == []


def test_slow_request_auto_dump(tracer):
    """An over-threshold *root* dumps its whole trace even at ratio 0."""
    tracer.configure(sample_ratio=0.0, slow_threshold_s=1.0)
    exp = InMemorySpanExporter()
    tracer.add_exporter(exp)

    now = time.monotonic()
    ctx = Context()
    # a fast trace exports nothing
    fast = tracer.start_span("frontend.request", trace=ctx.trace, root=True)
    fast.end()
    assert exp.spans == []

    # a slow trace flushes root + children still in the ring
    ctx2 = Context()
    tracer.record("engine.decode", ctx2,
                  start_mono=now - 4.0, end_mono=now - 0.5)
    root = tracer.start_span("frontend.request", trace=ctx2.trace, root=True)
    root.start_mono = now - 5.0
    root.end()
    names = sorted(s.name for s in exp.spans)
    assert names == ["engine.decode", "frontend.request"]
    assert all(s.trace_id == ctx2.trace.trace_id for s in exp.spans)


def test_record_derives_wall_anchor(tracer):
    """record() back-dates start_unix by the monotonic elapsed, so spans
    stamped in the past land at the right wall-clock position."""
    start = time.monotonic() - 2.0
    span = tracer.record("worker.queue", start_mono=start,
                         end_mono=start + 0.5)
    assert abs((time.time() - 2.0) - span.start_unix) < 0.1
    assert span.duration_s == pytest.approx(0.5)


def test_ring_buffer_bounded(tracer):
    tracer.configure(buffer_size=8)
    for i in range(32):
        tracer.start_span(f"s{i}").end()
    assert len(tracer.get_trace("nope")) == 0
    assert len(tracer.trace_ids(limit=100)) == 8


# --------------------- migration keeps one trace -------------------------


class FlakyEngine(AsyncEngine):
    """Streams 2 tokens then dies once; clean on the retry."""

    def __init__(self):
        self.calls = 0
        self.contexts = []

    async def generate(self, request, context):
        self.calls += 1
        self.contexts.append(context)
        start = len(request["token_ids"])
        n = int(request["max_tokens"])
        for i in range(n):
            yield {"token_ids": [100 + start + i],
                   "finished": i == n - 1,
                   "finish_reason": "length" if i == n - 1 else None,
                   "num_prompt_tokens": start}
            if self.calls == 1 and i == 1:
                raise EngineError("boom", ERR_UNAVAILABLE)


async def test_migration_attempts_share_one_trace(tracer):
    """A fault-migrated request stays ONE trace: each retry is a sibling
    migration.attempt child span under the request context, the failed one
    carrying the error status, the backoff nap its own span."""
    tracer.configure(sample_ratio=1.0)
    exp = InMemorySpanExporter()
    tracer.add_exporter(exp)

    flaky = FlakyEngine()
    mig = Migration(flaky, migration_limit=2, backoff_base_s=0.001)
    ctx = Context()
    out = [x async for x in mig.generate(
        {"token_ids": [1, 2, 3], "max_tokens": 5}, ctx)]
    assert out[-1]["finished"]

    spans = exp.by_trace()[ctx.trace.trace_id]
    attempts = [s for s in spans if s.name == "migration.attempt"]
    backoffs = [s for s in spans if s.name == "migration.backoff"]
    assert len(attempts) == 2 and len(backoffs) == 1
    # both attempts parent under the request context's span id
    assert {s.parent_span_id for s in attempts} == {ctx.trace.span_id}
    assert attempts[0].status == "error"
    assert attempts[0].status_detail == ERR_UNAVAILABLE
    assert attempts[1].status == "ok"
    # the attempt span's own id IS the attempt context's span id, so
    # downstream spans (router/transport) parent under the right attempt
    assert {s.span_id for s in attempts} == \
        {c.trace.span_id for c in flaky.contexts}
    # the failed attempt closed before the backoff nap started
    assert attempts[0].end_mono <= backoffs[0].start_mono
    # everything stayed in one trace
    assert len(exp.by_trace()) == 1


# --------------------------- offline assembly ----------------------------


def test_assembler_joins_and_dedupes(tracer, tmp_path):
    path_a = str(tmp_path / "front.jsonl")
    path_b = str(tmp_path / "worker.jsonl")
    tracer.configure(sample_ratio=1.0)
    tracer.add_jsonl(path_a)

    ctx = Context()
    root = tracer.start_span("frontend.request", trace=ctx.trace, root=True)
    child = tracer.start_span("frontend.tokenize", ctx)
    child.end()
    root.end()
    # the "worker" file repeats the child (slow-dump double export shape)
    with open(path_b, "w") as f:
        f.write(json.dumps(child.to_dict()) + "\n")
        f.write(json.dumps({**root.to_dict(),
                            "span_id": "feedfacefeedface",
                            "parent_span_id": root.span_id,
                            "name": "worker.ingress"}) + "\n")

    tracer.close()  # the export is buffered: files are read after close
    spans = load_spans([path_a, path_b])
    assert len(spans) == 3  # duplicate child collapsed
    traces = group_traces(spans)
    assembled = assemble_trace(traces[ctx.trace.trace_id])
    assert assembled["num_spans"] == 3
    by_name = {s["name"]: s for s in assembled["spans"]}
    assert by_name["frontend.request"]["depth"] == 0
    assert by_name["frontend.tokenize"]["depth"] == 1
    assert by_name["worker.ingress"]["depth"] == 1
    assert "frontend.tokenize" in assembled["stages"]
    text = render_trace(assembled)
    assert "stage breakdown:" in text and "frontend.request" in text


def test_assembler_cli(tracer, tmp_path, capsys):
    from dynamo_tpu.tracing.assemble import main

    path = str(tmp_path / "spans.jsonl")
    tracer.configure(sample_ratio=1.0)
    tracer.add_jsonl(path)
    ctx = Context()
    tracer.start_span("router.select", ctx).end()
    tracer.start_span("frontend.request", trace=ctx.trace, root=True).end()
    tracer.close()

    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "router.select" in out and ctx.trace.trace_id in out
    assert main([path, "--trace-id", "deadbeef"]) == 1
    assert main([path, "--trace-id", ctx.trace.trace_id, "--json"]) == 0
    assembled = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert assembled["trace_id"] == ctx.trace.trace_id


# ------------------------ aggregator staleness ---------------------------


def test_aggregator_expires_stale_workers():
    from types import SimpleNamespace

    from dynamo_tpu.metrics_aggregator import MetricsAggregator

    clock = {"t": 0.0}
    metrics = MetricsRegistry(prefix="trc_agg")
    runtime = SimpleNamespace(
        metrics=metrics,
        namespace=lambda *a, **k: SimpleNamespace(
            component=lambda name: SimpleNamespace(
                event_subject=lambda s: f"trc.{name}.{s}")),
    )
    agg = MetricsAggregator(runtime, "backend", stale_after_s=30.0,
                            clock=lambda: clock["t"])
    agg._on_stats({"worker_id": 1, "kv_usage": 0.2,
                   "prefix_cache_hits": 10, "prefix_cache_queries": 20})
    agg._on_stats({"worker_id": 2, "kv_usage": 0.8,
                   "prefix_cache_hits": 0, "prefix_cache_queries": 20})
    body = metrics.render().decode()
    assert 'worker="1"' in body and 'worker="2"' in body
    assert 'prefix_cache_hit_rate{component="backend"} 0.25' in body

    # worker 2 goes silent past the threshold; worker 1 keeps publishing
    clock["t"] = 31.0
    agg._on_stats({"worker_id": 1, "kv_usage": 0.3,
                   "prefix_cache_hits": 10, "prefix_cache_queries": 20})
    assert "2" not in agg.worker_stats and "2" not in agg._last_seen
    body = metrics.render().decode()
    assert 'worker="2"' not in body          # gauge label set cleared
    assert 'worker="1"' in body
    # hit rate recomputed over the survivors only
    assert 'prefix_cache_hit_rate{component="backend"} 0.5' in body


# -------------------------- recorder wall anchor -------------------------


async def test_recorder_carries_wall_anchor_and_trace_id(tmp_path):
    from dynamo_tpu.llm.recorder import Recorder

    path = str(tmp_path / "rec.jsonl")
    rec = Recorder(path=path)

    async def stream():
        yield {"token": 0}

    before = time.time()
    async for _ in rec.record_stream("r1", stream(), trace_id="ab" * 16):
        pass
    row = json.loads(open(path).read().splitlines()[0])
    assert row["trace_id"] == "ab" * 16
    assert before - 1.0 <= row["t_start_unix"] <= time.time()
    # trace_id stays optional: absent from the row when not provided
    rec2 = Recorder(path=path)
    async for _ in rec2.record_stream("r2", stream()):
        pass
    row2 = json.loads(open(path).read().splitlines()[1])
    assert "trace_id" not in row2 and "t_start_unix" in row2


# -------------------- e2e: crash, migrate, assemble ----------------------


@pytest.fixture
async def cluster(tmp_path):
    """store + 2 MockEngine workers on real ingress + KV-routed HTTP
    frontend with admission control, all sharing one process tracer."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.frontend.service import (
        HttpService, ModelEntry, ModelManager,
    )
    from dynamo_tpu.llm.discovery import ModelDeploymentCard
    from dynamo_tpu.llm.entrypoint import build_routed_pipeline, make_kv_sink
    from dynamo_tpu.mocker import MockEngine, MockerConfig
    from dynamo_tpu.router.kv_router import KvRouterConfig
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.store import StoreServer
    from dynamo_tpu.utils.config import RuntimeConfig

    from test_llm_pipeline import byte_tokenizer

    tracing.reset()
    store = StoreServer(host="127.0.0.1", port=0)
    await store.start()
    cfg = RuntimeConfig(store_addr=f"127.0.0.1:{store.port}")

    engines, serveds, runtimes = [], [], []
    for _ in range(2):
        rt = await DistributedRuntime.from_settings(cfg)
        engine = MockEngine(
            EngineConfig(block_size=4, num_blocks=64, max_model_len=256,
                         max_num_batched_tokens=256, max_num_seqs=8),
            MockerConfig(vocab_size=512, speedup_ratio=10.0),
        )
        await engine.start()
        ep = rt.namespace("trc").component("backend").endpoint("generate")
        serveds.append(await ep.serve_endpoint(engine))
        engines.append(engine)
        runtimes.append(rt)

    front_rt = await DistributedRuntime.from_settings(cfg)
    client = await (front_rt.namespace("trc").component("backend")
                    .endpoint("generate").client())
    await client.wait_for_instances(2, timeout_s=10.0)

    tk = byte_tokenizer()
    card = ModelDeploymentCard(
        name="tiny-chat", tokenizer_json=tk.to_json_str(),
        context_length=256, kv_block_size=4, migration_limit=2,
    )
    sink, router = await make_kv_sink(
        card, client, use_events=False, seed=0,
        config=KvRouterConfig(replica_sync=False, snapshot_threshold=0),
    )
    manager = ModelManager()
    manager.register(ModelEntry(
        name="tiny-chat",
        engine=build_routed_pipeline(card, client, sink=sink),
    ))
    service = HttpService(manager, host="127.0.0.1", port=0,
                          metrics=MetricsRegistry(prefix="trc_e2e"),
                          max_concurrent_requests=8)
    await service.start()

    # export everything: configured AFTER the runtimes so from_settings's
    # defaults (ratio 0) don't clobber the test knobs
    exporter = InMemorySpanExporter()
    jsonl_path = str(tmp_path / "spans.jsonl")
    tracer = tracing.get_tracer()
    tracer.configure(sample_ratio=1.0)
    tracer.add_exporter(exporter)
    tracer.add_jsonl(jsonl_path)

    yield {"service": service, "exporter": exporter, "jsonl": jsonl_path,
           "engines": engines, "tracer": tracer}

    await service.stop()
    await router.stop()
    await client.stop()
    for served in serveds:
        await served.stop()
    for engine in engines:
        await engine.stop()
    await front_rt.shutdown()
    for rt in runtimes:
        await rt.shutdown()
    await store.stop()
    tracing.reset()


# every stage the instrumented path must produce for a migrated request
E2E_STAGES = {
    "frontend.request", "frontend.admission", "frontend.tokenize",
    "migration.attempt", "migration.backoff", "router.select",
    "transport.send", "worker.ingress", "worker.queue",
    "engine.prefill", "engine.decode",
}
# pairwise-disjoint leaf windows: their summed time can never exceed the
# observed end-to-end latency
E2E_LEAVES = {
    "frontend.admission", "frontend.tokenize", "router.select",
    "worker.queue", "engine.prefill", "engine.decode", "migration.backoff",
}


@pytest.mark.e2e
async def test_e2e_trace_with_midstream_crash(cluster, tmp_path):
    """One request, one injected worker crash, one migration — and ONE
    assembled trace covering admission through decode on both workers."""
    from dynamo_tpu.runtime import faults

    plan = faults.FaultPlan(seed=0)
    plan.truncate_stream("worker.stream", match=None, after=3, times=1)
    faults.install(plan)
    t0 = time.monotonic()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{cluster['service'].port}"
                "/v1/chat/completions",
                json={"model": "tiny-chat", "max_tokens": 8,
                      "messages": [{"role": "user", "content": "hello"}]},
                timeout=aiohttp.ClientTimeout(total=60),
            ) as r:
                assert r.status == 200, await r.text()
                body = await r.json()
    finally:
        faults.clear()
    elapsed = time.monotonic() - t0
    assert plan.fired("worker.stream") == 1
    assert body["usage"]["completion_tokens"] == 8

    # worker-side engine spans and late parent closes land during stream
    # teardown — poll until the tree is complete (every stage present, both
    # attempts/ingresses exported, every parent resolvable)
    exporter = cluster["exporter"]

    def _complete() -> bool:
        snapshot = list(exporter.spans)
        names = [s.name for s in snapshot]
        if not (E2E_STAGES <= set(names)):
            return False
        if names.count("migration.attempt") < 2 \
                or names.count("worker.ingress") < 2:
            return False
        ids = {s.span_id for s in snapshot}
        return all(s.parent_span_id in ids for s in snapshot
                   if s.parent_span_id is not None)

    for _ in range(200):
        if _complete():
            break
        await asyncio.sleep(0.02)
    traces = exporter.by_trace()
    assert len(traces) == 1, f"expected ONE trace, got {list(traces)}"
    trace_id, spans = next(iter(traces.items()))
    names = {s.name for s in spans}
    assert E2E_STAGES <= names, f"missing stages: {E2E_STAGES - names}"

    by_id = {s.span_id: s for s in spans}
    roots = [s for s in spans if s.name == "frontend.request"]
    assert len(roots) == 1 and roots[0].parent_span_id is None
    # every non-root span links into the tree (worker roots hang off the
    # wire transport span, which is in the same export set)
    for s in spans:
        if s is roots[0]:
            continue
        assert s.parent_span_id in by_id, \
            f"{s.name} orphaned (parent {s.parent_span_id})"

    # the crashed attempt is visible: one errored migration.attempt with a
    # retry sibling, and the injected crash marked on the worker root
    attempts = sorted((s for s in spans if s.name == "migration.attempt"),
                      key=lambda s: s.start_mono)
    assert len(attempts) == 2
    assert attempts[0].status == "error" and attempts[1].status == "ok"
    ingresses = [s for s in spans if s.name == "worker.ingress"]
    assert len(ingresses) == 2
    assert sorted(s.status for s in ingresses) == ["error", "ok"]

    # disjoint leaf windows sum to no more than the observed e2e latency
    leaf_total = sum((s.duration_s or 0.0) for s in spans
                     if s.name in E2E_LEAVES)
    assert leaf_total <= elapsed + 0.01, (leaf_total, elapsed)

    # per-stage latency histograms reached the frontend Prometheus scrape
    scrape = cluster["service"].metrics.render().decode()
    assert 'trc_e2e_stage_latency_seconds_count{stage="frontend.request"}' \
        in scrape
    assert 'stage="engine.decode"' in scrape

    # the offline assembler reproduces the same single-trace picture
    cluster["tracer"].close()  # buffered export: read the file after close
    assembled = assemble_trace(
        group_traces(load_spans([cluster["jsonl"]]))[trace_id]
    )
    assert assembled["num_spans"] == len(spans)
    assert set(assembled["stages"]) == names
    assert "migration.attempt" in render_trace(assembled)


@pytest.mark.e2e
async def test_debug_trace_endpoint(cluster):
    """The system server serves assembled traces out of the live ring."""
    from dynamo_tpu.runtime.system_server import SystemServer

    async with aiohttp.ClientSession() as s:
        async with s.post(
            f"http://127.0.0.1:{cluster['service'].port}/v1/completions",
            json={"model": "tiny-chat", "prompt": "abc", "max_tokens": 4},
            timeout=aiohttp.ClientTimeout(total=60),
        ) as r:
            assert r.status == 200

    server = SystemServer(host="127.0.0.1", port=0)
    await server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/traces") as r:
                assert r.status == 200
                listing = await r.json()
            assert listing["count"] >= 1
            tid = listing["trace_ids"][0]
            async with s.get(f"{base}/debug/traces/{tid}") as r:
                assert r.status == 200
                assembled = await r.json()
            assert assembled["trace_id"] == tid
            assert assembled["num_spans"] >= 1
            async with s.get(f"{base}/debug/traces/{'0' * 32}") as r:
                assert r.status == 404
    finally:
        await server.stop()


@pytest.mark.e2e
async def test_e2e_road_stamps_reach_the_worker_span(cluster):
    """The frontend's accept stamp survives the pipeline's child contexts
    (migration) and the wire: ``worker.ingress`` carries ``upstream_s >=
    wire_s >= 0``, the same trace's ``worker.queue`` starts inside it
    before ``first_sent``, and the assembler's road summary reads every
    attr of the way in and the way out."""
    from dynamo_tpu.tracing.assemble import road_summary
    async with aiohttp.ClientSession() as s:
        async with s.post(
            f"http://127.0.0.1:{cluster['service'].port}/v1/completions",
            json={"model": "tiny-chat", "prompt": "hello there",
                  "max_tokens": 5},
            timeout=aiohttp.ClientTimeout(total=60),
        ) as r:
            assert r.status == 200, await r.text()
            assert (await r.json())["usage"]["completion_tokens"] == 5
    exporter = cluster["exporter"]
    for _ in range(200):
        if {"frontend.request", "worker.ingress", "worker.queue",
                "engine.decode"} <= {s.name for s in exporter.spans}:
            break
        await asyncio.sleep(0.02)
    [root] = [s for s in exporter.spans if s.name == "frontend.request"]
    [ing] = [s for s in exporter.spans if s.name == "worker.ingress"]
    assert root.trace_id == ing.trace_id
    assert 0.0 <= ing.attrs["wire_s"] <= ing.attrs["upstream_s"]
    assert ing.attrs["upstream_s"] <= root.duration_s
    assert ing.start_unix - ing.attrs["upstream_s"] == pytest.approx(
        root.start_unix, abs=1e-6)
    [queue] = [s for s in exporter.spans if s.name == "worker.queue"]
    at = {name: off for off, name, _ in ing.events}
    assert queue.trace_id == ing.trace_id
    assert (0.0 <= queue.start_mono - ing.start_mono <= at["first_sent"]
            <= ing.duration_s)
    assert ing.attrs["frames"] >= 5
    road = road_summary([s.to_dict() for s in exporter.spans])
    # (this cluster's engine is the mocker: no fetch lands, so no wake)
    assert set(road) == {
        "upstream_p50_ms", "wire_p50_ms", "frames", "send_mean_us",
        "send_max_ms", "sent_gap_p50_ms", "sent_gap_p95_ms"}
    assert road["frames"] == ing.attrs["frames"]
    assert road["upstream_p50_ms"] == pytest.approx(
        1e3 * ing.attrs["upstream_s"])
    assert 0.0 < road["send_mean_us"] <= 1e3 * road["send_max_ms"]
    assert road["sent_gap_p50_ms"] <= road["sent_gap_p95_ms"]
    text = render_trace(assemble_trace(
        [s.to_dict() for s in exporter.spans if s.trace_id == ing.trace_id]))
    assert "sent_gaps=p50<=" in text and "counts" not in text
    assert road_summary([root.to_dict()]) == {}


# ------------- PR 24: inside engine.prefill, buffered export -------------


async def test_prefill_span_events_and_queue_hit_attrs(tracer):
    """engine.prefill carries ``dispatched`` <= ``landed`` <= end (the
    prompt-completing chunk's enqueue and its landing on the fetch thread);
    worker.queue carries the prefix match made at admission."""
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.engine import InferenceEngine

    exporter = InMemorySpanExporter()
    tracer.configure(sample_ratio=1.0)
    tracer.add_exporter(exporter)
    engine = InferenceEngine(
        ModelConfig.tiny(),
        EngineConfig(block_size=4, num_blocks=64, max_num_seqs=4,
                     max_num_batched_tokens=64, max_model_len=128,
                     decode_buckets=(8,), prefill_buckets=(16,)),
    )
    await engine.start()
    prompt = list(range(3, 16))          # 13 tokens: 3 whole blocks of 4
    try:
        for _ in range(2):               # the second finds the first's blocks
            async for _ in engine.generate(
                    {"token_ids": prompt, "max_tokens": 4,
                     "ignore_eos": True}, Context()):
                pass
    finally:
        await engine.stop()
    prefills = [s for s in exporter.spans if s.name == "engine.prefill"]
    assert len(prefills) == 2
    for span in prefills:
        at = {name: off for off, name, _ in span.events}
        assert set(at) == {"dispatched", "landed"}
        assert 0.0 <= at["dispatched"] <= at["landed"] <= span.duration_s
    queues = [s for s in exporter.spans if s.name == "worker.queue"]
    assert [s.attrs["prompt_tokens"] for s in queues] == [13, 13]
    assert [s.attrs["cached_tokens"] for s in queues] == [0, 12]
    # events and attrs survive the JSONL form the benchmark reads
    back = tracing.Span.from_dict(json.loads(json.dumps(
        prefills[1].to_dict())))
    assert [e[1] for e in back.events] == ["dispatched", "landed"]
    decode = [s for s in exporter.spans if s.name == "engine.decode"]
    assert decode and all(
        set(s.attrs) == {"num_tokens", "wake_sum_s", "wake_max_s"}
        for s in decode)


def test_jsonl_span_export_is_buffered_and_complete_on_close(tracer, tmp_path):
    """No flush per span; ``SpanCollector.close()`` (runtime shutdown)
    leaves every exported span on disk, and a later span reopens the
    file."""
    path = str(tmp_path / "spans.jsonl")
    tracer.configure(sample_ratio=1.0)
    tracer.add_jsonl(path)
    t = time.monotonic()
    for i in range(6):
        tracer.record(f"stage.{i}", start_mono=t, end_mono=t + 0.001)
    assert len(load_spans([path])) < 6       # the rest is in the buffer
    tracer.close()
    assert [s["name"] for s in load_spans([path])] == [
        f"stage.{i}" for i in range(6)]
    tracer.record("stage.late", start_mono=t, end_mono=t + 0.001)
    tracer.close()
    assert len(load_spans([path])) == 7


async def test_worker_sigterm_path_flushes_span_export(tmp_path):
    """The SIGTERM path every worker shares (``run_until_shutdown``: drain,
    engine stop, runtime shutdown) closes the buffered exporter: all spans
    of the requests served are in the file after the process exits."""
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.utils.config import RuntimeConfig

    from test_llm_pipeline import byte_tokenizer
    from utils import ManagedProcess, free_port

    tok = tmp_path / "tokenizer.json"
    tok.write_text(byte_tokenizer().to_json_str())
    spans_path = tmp_path / "spans.jsonl"
    port = free_port()
    store = ManagedProcess(
        ["-m", "dynamo_tpu.runtime.store", "--host", "127.0.0.1",
         "--port", str(port)], name="store", ready_pattern=r"listening")
    worker = None
    n = 5
    try:
        store.wait_ready(20)
        worker = ManagedProcess(
            ["-m", "dynamo_tpu.mocker", "--model-name", "mock",
             "--tokenizer", str(tok), "--max-model-len", "512",
             "--speedup-ratio", "50"],
            name="mocker", ready_pattern=r"mocker ready",
            env={"DYNTPU_STORE_ADDR": f"127.0.0.1:{port}",
                 "DYNTPU_TRACE_SAMPLE_RATIO": "1",
                 "DYNTPU_TRACE_EXPORT_PATH": str(spans_path)})
        worker.wait_ready(60)
        rt = await DistributedRuntime.from_settings(
            RuntimeConfig(store_addr=f"127.0.0.1:{port}"))
        try:
            client = await (rt.namespace().component("backend")
                            .endpoint("generate").client())
            await client.wait_for_instances(1, timeout_s=10.0)
            for i in range(n):
                items = [item async for item in client.round_robin(
                    {"token_ids": [7 + i, 8, 9, 10], "max_tokens": 6,
                     "ignore_eos": True}, Context())]
                assert items and items[-1]["finished"]
            await client.stop()
        finally:
            await rt.shutdown()
        assert worker.terminate(timeout_s=30.0) == 0
    finally:
        if worker is not None:
            worker.kill()
        store.terminate()
    names = [s["name"] for s in load_spans([str(spans_path)])]
    for stage in ("worker.queue", "engine.prefill", "engine.decode"):
        assert names.count(stage) == n, (stage, names)


# ------------- PR 39: the per-stream gap histogram -------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_gap_histogram_tracks_exact_percentiles(seed):
    """A seeded sample of gaps (a body near 16 ms, a tail of chunked 60-200
    ms, a few strays under 1 ms and over 4 s): every percentile's bucket
    edge is at or above the exact value and less than one ratio above it;
    two histograms add bucket by bucket; the export describes itself."""
    import random

    from dynamo_tpu.tracing.hist import GapHistogram

    rng = random.Random(seed)
    xs = ([rng.gauss(0.016, 0.001) for _ in range(3000)]
          + [rng.uniform(0.06, 0.2) for _ in range(300)]
          + [rng.uniform(1e-4, 9e-4) for _ in range(20)]
          + [rng.uniform(4.2, 9.0) for _ in range(3)])
    rng.shuffle(xs)
    a, b = GapHistogram(), GapHistogram()
    for i, x in enumerate(xs):
        (a if i % 2 else b).add(x)
    da, db = a.to_dict(), b.to_dict()
    assert set(da) == {"lo_s", "ratio", "counts"}
    assert da["lo_s"] == 1e-3 and da["ratio"] == 2 ** 0.25
    assert len(da["counts"]) == 50      # < 1 ms, 48 buckets to 4.096 s, over
    both = {"lo_s": da["lo_s"], "ratio": da["ratio"],
            "counts": [x + y for x, y in zip(da["counts"], db["counts"])]}
    assert sum(both["counts"]) == len(xs)
    assert both["counts"][0] == 20 and both["counts"][-1] == 3

    def edge_of(q):
        """Bucket i ends at lo_s * ratio**i; the overflow bucket answers
        with where it starts."""
        seen = 0
        for i, n in enumerate(both["counts"]):
            seen += n
            if n and seen >= q / 100.0 * len(xs):
                return da["lo_s"] * da["ratio"] ** min(i, 48)

    v = sorted(xs)
    for q in (50, 90, 95, 99):
        exact = v[-(-len(v) * q // 100) - 1]        # nearest rank
        assert exact <= edge_of(q) * (1 + 1e-9)
        assert edge_of(q) < exact * da["ratio"]
    assert edge_of(100) == pytest.approx(4.096)
    assert json.loads(json.dumps(da)) == da
