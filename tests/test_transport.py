"""TCP request-push / response-stream transport: streaming, errors,
cancellation, multiplexing (capability contract of ref pipeline/network/*)."""

import asyncio

import pytest

from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import FnEngine
from dynamo_tpu.runtime.transport import (
    ERR_APP,
    ERR_DRAINING,
    ERR_OVERLOADED,
    ERR_UNAVAILABLE,
    EngineError,
    IngressServer,
    TransportClient,
)


async def echo_engine(request, context):
    for i in range(request["n"]):
        yield {"i": i, "msg": request["msg"]}


@pytest.fixture
async def served():
    server = IngressServer(FnEngine(echo_engine), host="127.0.0.1")
    await server.start()
    client = TransportClient()
    yield server, client, f"127.0.0.1:{server.port}"
    await client.close()
    await server.stop()


async def test_stream_roundtrip(served):
    _, client, addr = served
    out = [
        item
        async for item in client.generate(addr, {"n": 3, "msg": "hi"}, Context())
    ]
    assert out == [{"i": 0, "msg": "hi"}, {"i": 1, "msg": "hi"}, {"i": 2, "msg": "hi"}]


async def test_concurrent_multiplexed_streams(served):
    _, client, addr = served

    async def run(n):
        return [
            x["i"] async for x in client.generate(addr, {"n": n, "msg": "m"}, Context())
        ]

    results = await asyncio.gather(*(run(n) for n in (1, 5, 10, 2)))
    assert results == [list(range(n)) for n in (1, 5, 10, 2)]


async def test_application_error_propagates(served):
    server, client, addr = served

    async def failing(request, context):
        yield {"ok": 1}
        raise ValueError("boom")

    server._engine = FnEngine(failing)
    stream = client.generate(addr, {}, Context())
    assert (await stream.__anext__()) == {"ok": 1}
    with pytest.raises(EngineError) as exc_info:
        await stream.__anext__()
    assert exc_info.value.code == ERR_APP
    assert "boom" in str(exc_info.value)


async def test_connect_failure_is_retryable_error():
    client = TransportClient()
    with pytest.raises(EngineError) as exc_info:
        async for _ in client.generate("127.0.0.1:1", {}, Context()):
            pass
    assert exc_info.value.code == ERR_UNAVAILABLE


async def test_server_death_mid_stream_is_unavailable(served):
    server, client, addr = served

    async def slow(request, context):
        yield {"i": 0}
        await asyncio.sleep(30)
        yield {"i": 1}

    server._engine = FnEngine(slow)
    stream = client.generate(addr, {}, Context())
    assert (await stream.__anext__())["i"] == 0
    await server.stop()
    with pytest.raises(EngineError) as exc_info:
        await asyncio.wait_for(stream.__anext__(), 5)
    assert exc_info.value.code == ERR_UNAVAILABLE


async def test_graceful_stop_drains_partial_results(served):
    server, client, addr = served
    started = asyncio.Event()

    async def responsive(request, context):
        yield {"i": 0}
        started.set()
        while not context.is_stopped():
            await asyncio.sleep(0.01)
        yield {"final": True}

    server._engine = FnEngine(responsive)
    ctx = Context()
    stream = client.generate(addr, {}, ctx)
    assert (await stream.__anext__()) == {"i": 0}
    await started.wait()
    ctx.stop_generating()
    out = [item async for item in stream]
    assert out == [{"final": True}]


async def test_kill_abandons_stream(served):
    server, client, addr = served
    handler_killed = asyncio.Event()

    async def endless(request, context):
        try:
            i = 0
            while True:
                yield {"i": i}
                i += 1
                await asyncio.sleep(0.01)
        finally:
            if context.is_killed():
                handler_killed.set()

    server._engine = FnEngine(endless)
    ctx = Context()
    stream = client.generate(addr, {}, ctx)
    assert (await stream.__anext__())["i"] == 0
    ctx.kill()
    out = [item async for item in stream]
    assert len(out) <= 2  # nothing meaningful after kill
    await asyncio.wait_for(handler_killed.wait(), 5)


async def test_overload_rejection():
    release = asyncio.Event()

    async def blocker(request, context):
        await release.wait()
        yield {"done": True}

    server = IngressServer(FnEngine(blocker), host="127.0.0.1", max_inflight=1)
    await server.start()
    client = TransportClient()
    addr = f"127.0.0.1:{server.port}"
    try:
        first = client.generate(addr, {}, Context())
        task = asyncio.create_task(first.__anext__())
        await asyncio.sleep(0.1)  # let the first request take the slot
        with pytest.raises(EngineError) as exc_info:
            async for _ in client.generate(addr, {}, Context()):
                pass
        assert exc_info.value.code == ERR_OVERLOADED
        release.set()
        assert (await task) == {"done": True}
    finally:
        await client.close()
        await server.stop()


async def test_draining_rejects_new_requests(served):
    server, client, addr = served
    server.draining = True
    with pytest.raises(EngineError) as exc_info:
        async for _ in client.generate(addr, {"n": 1, "msg": "x"}, Context()):
            pass
    # draining is its own retryable code: routers divert instead of
    # counting it against the worker's circuit breaker
    assert exc_info.value.code == ERR_DRAINING


# ------------- PR 39: a request's way in, a token's way out --------------


@pytest.fixture
def spans():
    """Every span this test's requests end, from an isolated collector."""
    from dynamo_tpu import tracing

    exporter = tracing.InMemorySpanExporter()
    tracer = tracing.reset()
    tracer.configure(sample_ratio=1.0)
    tracer.add_exporter(exporter)
    yield exporter.spans
    tracing.reset()


async def test_ingress_span_stamps_way_in_and_way_out(spans):
    """One request through TransportClient -> IngressServer: the ingress span
    says how long the way in was (``upstream_s`` from the front door's
    stamp, ``wire_s`` from the client's write) and what the stream's own
    socket saw of the way out: ``frames``, ``send_sum_s`` / ``send_max_s``,
    ``first_sent``
    and the gaps between send completions as a histogram whose counts sum
    to ``frames - 1`` and whose p95 is within one bucket of the exact."""
    import contextlib
    import time

    from benchmarks.chip.metrics import percentile

    pauses = [0.02] * 18 + [0.08] * 2      # the tail is the two long ones
    wrote = []                             # when each data frame was written

    @contextlib.contextmanager
    def send_phase():
        yield
        wrote.append(time.monotonic())

    async def slow_engine(request, context):
        yield {"i": -1}
        for i, pause in enumerate(request["pauses"]):
            await asyncio.sleep(pause)
            yield {"i": i}

    server = IngressServer(FnEngine(slow_engine), host="127.0.0.1",
                           send_phase=send_phase)
    await server.start()
    client = TransportClient()
    try:
        ctx = Context()
        ctx.accepted_unix = time.time() - 0.25   # the front door, 250 ms ago
        out = [x["i"] async for x in client.generate(
            f"127.0.0.1:{server.port}", {"pauses": pauses}, ctx)]
    finally:
        await client.close()
        await server.stop()
    assert out == [-1] + list(range(len(pauses)))
    [ing] = [s for s in spans if s.name == "worker.ingress"]
    a = ing.attrs
    assert 0.25 <= a["upstream_s"] < 1.0
    assert 0.0 <= a["wire_s"] < a["upstream_s"]
    at = {name: off for off, name, _ in ing.events}
    assert 0.0 < at["first_sent"] <= ing.duration_s
    assert a["frames"] == len(pauses) + 1
    assert 0.0 < a["send_max_s"] <= a["send_sum_s"] < ing.duration_s
    hist = a["sent_gaps"]
    assert set(hist) == {"lo_s", "ratio", "counts"}
    assert hist["ratio"] <= 2 ** 0.25 and hist["lo_s"] == 1e-3
    assert sum(hist["counts"]) == a["frames"] - 1
    exact = percentile([b - c for c, b in zip(wrote, wrote[1:])], 95)
    # bucket i ends at lo_s * ratio**i: the first whose running count
    # reaches 95% of the gaps
    need, seen = 0.95 * sum(hist["counts"]), 0
    for i, n in enumerate(hist["counts"]):
        seen += n
        if n and seen >= need:
            break
    edge = hist["lo_s"] * hist["ratio"] ** i
    assert edge / hist["ratio"] ** 2 <= exact <= edge * hist["ratio"]
    assert 0.07 < edge < 0.2


async def test_ingress_span_without_stamps_has_no_way_in_attrs(spans):
    """A caller that sends neither stamp (here: a bare frame, no headers)
    gets a span without ``upstream_s`` / ``wire_s`` (absent, not zero); a
    TransportClient caller that was never stamped at a front door gets
    ``wire_s`` alone."""
    import msgpack

    from dynamo_tpu.runtime.store import read_frame, write_frame

    server = IngressServer(FnEngine(echo_engine), host="127.0.0.1")
    await server.start()
    client = TransportClient()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        write_frame(writer, {"t": "req", "rid": "bare", "payload":
                             msgpack.packb({"n": 2, "msg": "m"})})
        await writer.drain()
        kinds = [(await read_frame(reader))["t"] for _ in range(3)]
        writer.close()
        assert kinds == ["data", "data", "end"]
        async for _ in client.generate(f"127.0.0.1:{server.port}",
                                       {"n": 2, "msg": "m"}, Context()):
            pass
    finally:
        await client.close()
        await server.stop()
    bare, plain = [s for s in spans if s.name == "worker.ingress"]
    assert bare.attrs["rid"] == "bare" and bare.attrs["frames"] == 2
    assert "upstream_s" not in bare.attrs and "wire_s" not in bare.attrs
    assert "upstream_s" not in plain.attrs and plain.attrs["wire_s"] >= 0.0
    assert [name for _, name, _ in bare.events] == ["first_sent"]


async def test_unsampled_stream_takes_no_per_frame_stamps():
    """The way out is stamped per frame only where an exporter may take the
    span (``SpanCollector.keeps``): with an exporter but the trace not
    sampled and no slow threshold, the ingress span carries the way in
    (stamped once a request) and nothing per frame, and the profiler
    annotation is never entered; a slow threshold turns the stamps on."""
    import contextlib

    from dynamo_tpu import tracing

    entered = []

    @contextlib.contextmanager
    def send_phase():
        entered.append(1)
        yield

    async def one(tracer):
        server = IngressServer(FnEngine(echo_engine), host="127.0.0.1",
                               send_phase=send_phase)
        await server.start()
        client = TransportClient()
        try:
            ctx = Context()
            out = [x async for x in client.generate(
                f"127.0.0.1:{server.port}", {"n": 3, "msg": "m"}, ctx)]
        finally:
            await client.close()
            await server.stop()
        assert len(out) == 3
        [span] = [s for s in tracer.get_trace(ctx.trace.trace_id)
                  if s.name == "worker.ingress"]
        return span

    tracer = tracing.reset()
    try:
        tracer.add_exporter(tracing.InMemorySpanExporter())
        assert not tracer.keeps("0" * 32)
        span = await one(tracer)
        assert "wire_s" in span.attrs and "frames" not in span.attrs
        assert not span.events and not entered
        tracer.configure(slow_threshold_s=60.0)
        assert tracer.keeps("0" * 32)
        span = await one(tracer)
        assert span.attrs["frames"] == 3 and len(entered) == 3
    finally:
        tracing.reset()
