"""Run-ahead (pipelined) engine: deep pipelines produce the same tokens as
the synchronous engine, EOS with windows in flight reaps cleanly, and
slots/blocks are recycled. CPU."""

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request

pytestmark = pytest.mark.anyio


def _cfg(pipeline_depth=2, **kw):
    base = dict(
        num_blocks=128, max_model_len=256, max_num_batched_tokens=64,
        prefill_buckets=(64,), decode_buckets=(8,), max_num_seqs=8,
    )
    base.update(kw)
    return EngineConfig(pipeline_depth=pipeline_depth, **base)


async def _collect(engine, req):
    toks = []
    async for out in engine.submit(req):
        toks.append(out.token_id)
    return toks


def _mk_req(i, n_prompt=10, max_tokens=12, **kw):
    rng = np.random.default_rng(100 + i)
    return Request(
        request_id=f"r{i}",
        token_ids=[int(t) for t in rng.integers(1, 250, size=n_prompt)],
        max_tokens=max_tokens, ignore_eos=kw.pop("ignore_eos", True), **kw,
    )


async def test_pipelined_matches_sync():
    """Same prompts, greedy: depth-3 pipelined == depth-1 sync."""
    mc = ModelConfig.tiny()
    import asyncio

    ref_engine = InferenceEngine(mc, _cfg(1), seed=0)
    ref = [await _collect(ref_engine, _mk_req(i)) for i in range(4)]
    await ref_engine.stop()

    eng = InferenceEngine(mc, _cfg(3), seed=0)
    got = await asyncio.gather(*(
        _collect(eng, _mk_req(i)) for i in range(4)
    ))
    await eng.stop()
    assert [list(g) for g in got] == ref


async def test_eos_mid_window_reaps():
    """A seq that stops (EOS honoured) while later windows are in flight
    discards their tokens; its slot and blocks come back once they land."""
    mc = ModelConfig.tiny()
    eng = InferenceEngine(mc, _cfg(3), seed=0)
    # run one greedy request to learn its token stream
    probe = await _collect(eng, _mk_req(0, max_tokens=16))
    eos = probe[5]  # force EOS at output index 5 (windows 6, 7 in flight)
    req = _mk_req(0, max_tokens=16, ignore_eos=False)
    req.eos_token_ids = (eos,)
    toks = await _collect(eng, req)
    assert toks == probe[:6]  # stopped AT the eos token
    # engine drains: all pendings land; scheduler fully recycled
    import asyncio
    for _ in range(100):
        s = eng.scheduler
        if (not s.zombies and not s.running
                and len(s._free_slots) == eng.config.max_num_seqs):
            break
        await asyncio.sleep(0.05)
    assert not eng.scheduler.zombies
    assert len(eng.scheduler._free_slots) == eng.config.max_num_seqs
    free_before = eng.scheduler.pool.num_free
    await eng.stop()
    assert free_before == eng.scheduler.pool.num_free


async def test_seeded_sampling_pipelined():
    """Per-request seeded stochastic decode is reproducible under the
    pipelined loop (position-keyed row rngs)."""
    mc = ModelConfig.tiny()
    eng = InferenceEngine(mc, _cfg(3), seed=0)
    a = await _collect(eng, _mk_req(1, temperature=0.9, seed=42))
    b = await _collect(eng, _mk_req(1, temperature=0.9, seed=42))
    c = await _collect(eng, _mk_req(1, temperature=0.9, seed=43))
    await eng.stop()
    assert a == b
    assert a != c


async def test_starved_budget_seatmap_rebuild():
    """Block-pool starvation forces LIVE seqs to be skipped in some decode
    rounds. A skipped-but-live seat must NOT keep its column in a reused
    device seat map — the window kernel would advance its device-side
    pos/ring token past the host mirror, corrupting the stream when
    the seq is scheduled again. Greedy outputs must match the unstarved
    synchronous engine exactly."""
    import asyncio

    mc = ModelConfig.tiny()
    reqs = [
        dict(n_prompt=6 + i % 3, max_tokens=8 + i % 5) for i in range(6)
    ]
    ref_engine = InferenceEngine(mc, _cfg(1), seed=0)
    ref = [await _collect(ref_engine, _mk_req(i, **kw))
           for i, kw in enumerate(reqs)]
    await ref_engine.stop()

    # 3 prompt tokens a round beside 6 decoding seqs, 8 blocks vs ~12 needed
    eng = InferenceEngine(
        mc,
        _cfg(3, max_num_batched_tokens=3, num_blocks=8,
             prefill_buckets=(8,), max_model_len=64),
        seed=0,
    )

    async def one(i, kw):
        await asyncio.sleep(0.005 * i)
        return await _collect(eng, _mk_req(i, **kw))

    got = await asyncio.gather(*(one(i, kw) for i, kw in enumerate(reqs)))
    await eng.stop()
    assert [list(g) for g in got] == ref


async def test_many_requests_slot_churn():
    """More requests than slots, staggered arrivals: every request
    completes with the right token count and the pool drains clean."""
    import asyncio

    mc = ModelConfig.tiny()
    eng = InferenceEngine(mc, _cfg(4), seed=0)

    async def one(i):
        await asyncio.sleep(0.01 * (i % 5))
        return await _collect(
            eng, _mk_req(i, n_prompt=6 + i % 7, max_tokens=5 + i % 9)
        )

    outs = await asyncio.gather(*(one(i) for i in range(24)))
    for i, toks in enumerate(outs):
        assert len(toks) == 5 + i % 9, (i, len(toks))
    for _ in range(100):
        if (not eng.scheduler.zombies
                and len(eng.scheduler._free_slots)
                == eng.config.max_num_seqs):
            break
        await asyncio.sleep(0.05)
    assert len(eng.scheduler._free_slots) == eng.config.max_num_seqs
    await eng.stop()
