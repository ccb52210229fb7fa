"""Chunked prefill e2e: with ``prefill_chunk_tokens`` set, long prompts are
admitted in budget-capped slices interleaved with running decodes — and the
output streams stay byte-identical to whole-prompt prefill, greedy and
seeded sampling, speculative decoding included. Also covers the ragged
Pallas path serving the chunks (interpret mode) and the scheduler's
chunk-cap accounting. CPU."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request
from dynamo_tpu.engine.scheduler import Scheduler

pytestmark = pytest.mark.anyio


def _cfg(**kw):
    base = dict(
        num_blocks=128, max_model_len=256, max_num_batched_tokens=64,
        prefill_buckets=(16, 32, 64), decode_buckets=(8,), max_num_seqs=8,
        pipeline_depth=1,
    )
    base.update(kw)
    return EngineConfig(**base)


def _mk_req(i, n_prompt=50, max_tokens=12, **kw):
    rng = np.random.default_rng(100 + i)
    return Request(
        request_id=f"r{i}",
        token_ids=[int(t) for t in rng.integers(1, 250, size=n_prompt)],
        max_tokens=max_tokens, ignore_eos=kw.pop("ignore_eos", True), **kw,
    )


async def _collect_all(engine, reqs):
    async def one(r):
        toks = []
        async for out in engine.submit(r):
            toks.append(out.token_id)
        return toks
    try:
        return await asyncio.gather(*(one(r) for r in reqs))
    finally:
        await engine.stop()


async def _run(ec, reqs=None):
    if reqs is None:
        reqs = [_mk_req(i) for i in range(4)]
    engine = InferenceEngine(ModelConfig.tiny(), ec, seed=0)
    return await _collect_all(engine, reqs)


async def test_chunked_prefill_byte_identical():
    ref = await _run(_cfg())
    chunked = await _run(_cfg(prefill_chunk_tokens=16))
    assert chunked == ref


async def test_chunked_prefill_pallas_byte_identical():
    # chunks served by the ragged Pallas kernel (interpret mode on CPU)
    ref = await _run(_cfg())
    chunked = await _run(_cfg(prefill_chunk_tokens=16,
                              attention_impl_prefill="pallas"))
    assert chunked == ref


async def test_chunked_prefill_with_spec_byte_identical():
    # spec decoding on: verify windows ride the same unified steps as the
    # prefill chunks; streams must not change
    ref = await _run(_cfg())
    spec = await _run(_cfg(spec_mode="ngram", spec_k=4))
    chunked = await _run(_cfg(
        spec_mode="ngram", spec_k=4, prefill_chunk_tokens=16,
        attention_impl_spec="pallas", attention_impl_prefill="pallas",
    ))
    assert spec == ref
    assert chunked == ref


async def test_chunked_prefill_sampled_byte_identical():
    # per-request seeded sampling is deterministic per token INDEX, so
    # chunking (which only changes prefill slicing) must not perturb it
    reqs = [_mk_req(i, temperature=0.8, seed=7 + i) for i in range(4)]
    ref = await _run(_cfg(), reqs)
    reqs = [_mk_req(i, temperature=0.8, seed=7 + i) for i in range(4)]
    chunked = await _run(_cfg(prefill_chunk_tokens=16), reqs)
    assert chunked == ref


async def test_chunk_cap_respected():
    # the scheduler never emits a prefill chunk above the cap (but pads
    # nothing below one block)
    sched = Scheduler(_cfg(prefill_chunk_tokens=16))
    from dynamo_tpu.engine.scheduler import SchedSeq

    seq = SchedSeq(seq_id="s0", prompt_ids=list(range(1, 51)),
                   max_tokens=4, eos_token_ids=frozenset())
    sched.add(seq)
    seen = 0
    for _ in range(10):
        batch = sched.schedule()
        for c in batch.prefills:
            assert c.length <= 16
            seen += c.length
            sched.on_prefill_executed(c, 1 if c.final else None)
        if seen >= 50:
            break
    assert seen == 50


async def test_interleaves_with_decode():
    # a long prompt arriving while decodes run is admitted in chunks in
    # the SAME schedule rounds as the running decodes — the whole-prompt
    # stall this feature removes would schedule no decode seats instead
    ec = _cfg(prefill_chunk_tokens=16, max_num_batched_tokens=32)
    engine = InferenceEngine(ModelConfig.tiny(), ec, seed=0)

    async def short(i):
        toks = []
        async for out in engine.submit(_mk_req(i, n_prompt=8,
                                               max_tokens=24)):
            toks.append(out.token_id)
        return toks

    async def long_one():
        await asyncio.sleep(0.05)  # let the short ones reach decode
        toks = []
        async for out in engine.submit(_mk_req(99, n_prompt=64,
                                               max_tokens=4)):
            toks.append(out.token_id)
        return toks

    try:
        results = await asyncio.gather(short(0), short(1), long_one())
    finally:
        await engine.stop()
    assert all(len(r) > 0 for r in results)
    # the long prompt needed ceil(64/16) = 4 chunk dispatches
    assert engine.num_prefill_dispatches >= 6  # 2 shorts + 4 chunks
