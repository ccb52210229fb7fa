"""Scheduler semantics: block pool accounting, admission, chunked prefill,
prefix caching, preemption (the contract encoded in ref mocker/scheduler.rs)."""

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import (
    BlockPool, KvEvent, SchedSeq, Scheduler, SeqStatus,
)


def make_config(**kw):
    defaults = dict(
        block_size=4, num_blocks=17, max_num_seqs=8,
        max_num_batched_tokens=32, max_model_len=64,
        decode_buckets=(8,), prefill_buckets=(32,),
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def make_seq(seq_id, prompt, **kw):
    defaults = dict(max_tokens=8, eos_token_ids=frozenset())
    defaults.update(kw)
    return SchedSeq(seq_id=seq_id, prompt_ids=list(prompt), **defaults)


# ----------------------------- BlockPool ---------------------------------


def test_pool_allocate_free_cycle():
    pool = BlockPool(5)  # blocks 1..4 usable
    bids = [pool.allocate() for _ in range(4)]
    assert sorted(bids) == [1, 2, 3, 4]
    assert pool.allocate() is None
    pool.decref(bids[0])
    assert pool.allocate() == bids[0]


def test_pool_seal_reuse_and_evict():
    events = []
    pool = BlockPool(4, on_event=events.append)
    a = pool.allocate()
    pool.seal(a, seq_hash=111, block_hash=11, parent=None)
    pool.decref(a)  # sealed → evictable, not free
    assert pool.lookup(111) == a          # prefix-cache hit revives it
    pool.decref(a)
    b = pool.allocate()                    # free list first
    c = pool.allocate()
    d = pool.allocate()                    # pool dry → evicts sealed block a
    assert d == a
    assert pool.lookup(111) is None        # content gone
    kinds = [e.kind for e in events]
    assert kinds == ["stored", "removed"]


def test_pool_usage():
    pool = BlockPool(5)
    assert pool.usage == 0.0
    pool.allocate()
    assert abs(pool.usage - 0.25) < 1e-9


# ----------------------------- Scheduler ---------------------------------


def test_prefill_then_decode_flow():
    sched = Scheduler(make_config())
    seq = make_seq("a", range(100, 110))  # 10 tokens
    sched.add(seq)
    batch = sched.schedule()
    assert len(batch.prefills) == 1
    chunk = batch.prefills[0]
    assert (chunk.start, chunk.length) == (0, 10)
    assert chunk.completes_prompt
    assert len(seq.block_table) == 3  # ceil(10/4)
    sched.on_prefill_executed(chunk, sampled=7)
    assert seq.output_ids == [7]
    assert seq.num_computed == 10
    # two full blocks sealed (8 tokens), third partial
    assert seq.num_sealed_blocks == 2

    batch2 = sched.schedule()
    assert batch2.prefills == [] and batch2.decodes == [seq]
    sched.on_decode_executed(seq, sampled=8)
    assert seq.output_ids == [7, 8]
    assert seq.num_computed == 11


def test_chunked_prefill_budget():
    sched = Scheduler(make_config(max_num_batched_tokens=8))
    seq = make_seq("a", range(100, 120))  # 20 tokens > budget 8
    sched.add(seq)
    b1 = sched.schedule()
    assert (b1.prefills[0].start, b1.prefills[0].length) == (0, 8)
    assert not b1.prefills[0].completes_prompt
    sched.on_prefill_executed(b1.prefills[0], None)
    b2 = sched.schedule()
    assert (b2.prefills[0].start, b2.prefills[0].length) == (8, 8)
    sched.on_prefill_executed(b2.prefills[0], None)
    b3 = sched.schedule()
    assert (b3.prefills[0].start, b3.prefills[0].length) == (16, 4)
    assert b3.prefills[0].completes_prompt


def test_decode_rows_take_no_prefill_budget():
    sched = Scheduler(make_config(max_num_batched_tokens=4))
    a = make_seq("a", range(4))
    sched.add(a)
    sched.on_prefill_executed(sched.schedule().prefills[0], sampled=1)
    b = make_seq("b", range(200, 220))
    sched.add(b)
    batch = sched.schedule()
    assert batch.decodes == [a]
    # the budget is prompt tokens: a's decode row rides another program
    assert batch.prefills[0].length == 4


def _decoding_scheduler(rows, **kw):
    """A scheduler with ``rows`` sequences past their prefill, each with
    tokens left to decode."""
    sched = Scheduler(make_config(**kw))
    for i in range(rows):
        seq = make_seq(f"d{i}", range(1000 + 16 * i, 1008 + 16 * i),
                       max_tokens=10_000)
        sched.add(seq)
        for c in sched.schedule().prefills:
            sched.on_prefill_executed(c, 1 if c.final else None)
        # land the decode rows planned beside the prefill
        for s in sched.running:
            while s.pending_decode:
                sched.on_decode_executed(s, 1)
    assert len(sched.running) == rows
    return sched


@pytest.mark.parametrize("prompt_len", [100, 512, 513, 1100, 4096])
@pytest.mark.parametrize("rows", [0, 8, 64, 127])
def test_chunks_are_whole_buckets_whatever_decodes(rows, prompt_len):
    budget = 512
    sched = _decoding_scheduler(
        rows, block_size=16, num_blocks=2048, max_num_seqs=128,
        max_num_batched_tokens=budget, max_model_len=8192,
        decode_buckets=(128,), prefill_buckets=(16, 32, 64, 128, 256, 512),
        enable_prefix_caching=False,
    )
    decoding = list(sched.running)
    seq = make_seq("p", range(prompt_len), max_tokens=4)
    sched.add(seq)
    chunks = []
    while seq.status is not SeqStatus.RUNNING:
        batch = sched.schedule()
        assert sum(c.length for c in batch.prefills) <= budget
        # every running row still gets its decode row
        assert [r.seq for r in batch.decode_rows] == decoding
        for row in batch.decode_rows:
            for _ in range(row.accepted):
                sched.on_decode_executed(row.seq, 1)
        for c in batch.prefills:
            chunks.append(c.length)
            sched.on_prefill_executed(c, 1 if c.final else None)
    assert len(chunks) == -(-prompt_len // 512)
    assert all(n == 512 for n in chunks[:-1])
    assert sum(chunks) == prompt_len


def test_prompt_admitted_when_seats_outnumber_budget():
    # max_num_seqs >= max_num_batched_tokens: were a decode row charged a
    # token, nothing could prefill while every other seat decodes
    sched = _decoding_scheduler(7, max_num_seqs=8, max_num_batched_tokens=8,
                                num_blocks=64, prefill_buckets=(8,))
    seq = make_seq("p", range(500, 506))
    sched.add(seq)
    batch = sched.schedule()
    assert len(batch.decode_rows) == 7
    assert [(c.seq, c.length, c.final) for c in batch.prefills] == [
        (seq, 6, True)]


def test_prefix_cache_reuse():
    sched = Scheduler(make_config())
    a = make_seq("a", range(100, 112))  # 3 full blocks
    sched.add(a)
    chunk = sched.schedule().prefills[0]
    sched.on_prefill_executed(chunk, sampled=1)
    sched.finish(a, "stop")  # blocks sealed + evictable

    # same 8-token prefix, new tail
    b = make_seq("b", list(range(100, 108)) + [999, 998])
    sched.add(b)
    batch = sched.schedule()
    c = batch.prefills[0]
    assert b.num_computed == 8            # two blocks reused
    assert (c.start, c.length) == (8, 2)
    assert b.block_table[:2] == a.block_table[:2] or len(b.block_table) == 3
    assert sched.stats.prefix_cache_hits == 2


def test_fully_cached_prompt_recomputes_last_token():
    sched = Scheduler(make_config())
    a = make_seq("a", range(100, 108))  # exactly 2 blocks
    sched.add(a)
    sched.on_prefill_executed(sched.schedule().prefills[0], sampled=1)
    sched.finish(a, "stop")
    b = make_seq("b", range(100, 108))   # identical prompt
    sched.add(b)
    chunk = sched.schedule().prefills[0]
    # only 1 block may be reused: the last token must be recomputed
    assert b.num_computed == 4
    assert (chunk.start, chunk.length) == (4, 4)


def test_preemption_recompute():
    # pool: 16 usable blocks; two seqs of 8 tokens → 2 blocks each + growth
    sched = Scheduler(make_config(num_blocks=9, watermark=0.0))  # 8 usable
    a = make_seq("a", range(100, 116), max_tokens=64)  # 4 blocks
    b = make_seq("b", range(200, 216), max_tokens=64)  # 4 blocks
    sched.add(a)
    sched.add(b)
    batch = sched.schedule()
    for c in batch.prefills:
        sched.on_prefill_executed(c, sampled=1)
    assert len(sched.running) == 2
    # drive decodes until the pool runs dry → b (newest) preempted
    preempted = None
    for _ in range(20):
        batch = sched.schedule()
        if batch.preempted:
            preempted = batch.preempted[0]
            break
        for s in batch.decodes:
            sched.on_decode_executed(s, sampled=1)
    assert preempted is b
    assert b.preemptions == 1
    # preemption may be followed by immediate re-admission as prefill within
    # the same schedule() call, so status is WAITING or PREFILL
    assert b.status in (SeqStatus.WAITING, SeqStatus.PREFILL)
    assert b.output_ids  # generated tokens survive preemption (recompute)
    # a keeps decoding
    assert a in sched.running


def test_finish_releases_blocks():
    sched = Scheduler(make_config())
    seq = make_seq("a", range(10))
    sched.add(seq)
    sched.on_prefill_executed(sched.schedule().prefills[0], sampled=1)
    used_before = sched.pool.num_free
    sched.finish(seq, "stop")
    assert sched.pool.num_free > used_before
    assert seq.status == SeqStatus.FINISHED


def test_stop_conditions():
    sched = Scheduler(make_config())
    seq = make_seq("a", range(10), max_tokens=2, eos_token_ids=frozenset({5}))
    sched.add(seq)
    sched.on_prefill_executed(sched.schedule().prefills[0], sampled=9)
    assert sched.check_stop(seq) is None
    sched.on_decode_executed(seq, sampled=5)
    assert sched.check_stop(seq) == "stop"      # eos
    seq2 = make_seq("b", range(10), max_tokens=2)
    sched.add(seq2)
    seq2.output_ids = [1, 2]
    assert sched.check_stop(seq2) == "length"   # max_tokens


def test_kv_events_stored_and_removed():
    events = []
    sched = Scheduler(make_config(), on_event=events.append)
    seq = make_seq("a", range(100, 108))
    sched.add(seq)
    sched.on_prefill_executed(sched.schedule().prefills[0], sampled=1)
    stored = [e for e in events if e.kind == "stored"]
    assert len(stored) == 2
    # chained hashes: second block's parent is first block's seq_hash
    assert stored[1].blocks[0]["parent"] == stored[0].blocks[0]["seq_hash"]


def test_preempted_seq_not_double_scheduled():
    """A seq preempted mid-decode-loop by an earlier seq's slot allocation
    must not also be scheduled as a decode (and then again as a prefill) in
    the same schedule() call."""
    sched = Scheduler(make_config(num_blocks=9, watermark=0.0))  # 8 usable
    a = make_seq("a", range(100, 116), max_tokens=64)  # 4 blocks
    b = make_seq("b", range(200, 216), max_tokens=64)  # 4 blocks
    sched.add(a)
    sched.add(b)
    for c in sched.schedule().prefills:
        sched.on_prefill_executed(c, sampled=1)
    for _ in range(20):
        batch = sched.schedule()
        decode_ids = [s.seq_id for s in batch.decodes]
        assert len(decode_ids) == len(set(decode_ids))
        for s in batch.decodes:
            # a decode must always target a RUNNING seq with a valid slot
            assert s.status is SeqStatus.RUNNING
            assert len(s.block_table) * 4 > s.num_computed
        prefill_ids = {c.seq.seq_id for c in batch.prefills}
        assert not prefill_ids & set(decode_ids)
        for s in batch.decodes:
            sched.on_decode_executed(s, sampled=1)
        for c in batch.prefills:
            sched.on_prefill_executed(c, sampled=1 if c.completes_prompt else None)
    # no physical block is referenced by two live seqs
    live = [s for s in (a, b) if s.status is not SeqStatus.FINISHED]
    all_bids = [bid for s in live for bid in s.block_table]
    assert len(all_bids) == len(set(all_bids))


def test_pool_clear_keeps_referenced_blocks():
    """clear() must not return blocks still referenced by running seqs."""
    pool = BlockPool(6)
    a = pool.allocate()
    b = pool.allocate()
    pool.seal(b, seq_hash=42, block_hash=4, parent=None)
    pool.decref(b)          # b → evictable (prefix cache)
    pool.clear()
    # a is still referenced: allocate() must never hand it out again
    got = [pool.allocate() for _ in range(4)]
    assert a not in got
    assert None not in got  # b plus the remaining free blocks are available
    assert pool.lookup(42) is None  # cache gone
    pool.decref(a)          # release → now reusable
    assert pool.allocate() == a
