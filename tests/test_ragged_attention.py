"""Ragged paged-attention kernel: CPU interpret-mode parity vs a numpy
reference across mixed shape classes (decode rows, spec windows, prefill
chunks), GQA group sizes, partial last blocks, seat churn, and the
trash-block / NaN-poisoning contract. Runs without TPU hardware."""

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.ops.paged_attention import (
    paged_attention_decode, paged_attention_ragged,
)

pytestmark = pytest.mark.kernel


def _reference(q, k_cache, v_cache, tables, q_start, q_len, ctx_len, bs):
    """Loop-nest reference: query i of row r sits at absolute position
    ctx_len[r] - q_len[r] + i and sees key positions <= that."""
    Tq, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    out = np.zeros_like(q, dtype=np.float32)
    for r in range(len(q_len)):
        cl = int(ctx_len[r])
        keys = np.zeros((cl, KV, hd), np.float32)
        vals = np.zeros((cl, KV, hd), np.float32)
        for pos in range(cl):
            blk, off = int(tables[r, pos // bs]), pos % bs
            keys[pos] = k_cache[blk, :, off]
            vals[pos] = v_cache[blk, :, off]
        for i in range(int(q_len[r])):
            vis = cl - int(q_len[r]) + i + 1
            for h in range(H):
                kv = h // G
                s = keys[:vis, kv] @ q[q_start[r] + i, h] / np.sqrt(hd)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[q_start[r] + i, h] = p @ vals[:vis, kv]
    return out


def _make_case(rows, *, G=2, KV=2, hd=64, bs=16, W=8, q_tile=4, seed=0,
               poison_trash=True, poison_tails=True):
    """Build a ragged batch. ``rows`` is a list of (q_len, ctx_len,
    alloc_tiles). Block tables are allocated contiguously from block 1;
    the trash block 0 and (optionally) the dead tail of each partial last
    block are filled with NaN to assert they can never leak."""
    rng = np.random.default_rng(seed)
    H = KV * G
    q_start = [0]
    for ql, cl, al in rows:
        assert ql <= al * q_tile <= max(al * q_tile, 1)
        q_start.append(q_start[-1] + al * q_tile)
    Tq = q_start[-1]
    nb = 1 + sum((cl + bs - 1) // bs for _, cl, _ in rows) + 2
    q = rng.standard_normal((Tq, H, hd)).astype(np.float32)
    k_cache = rng.standard_normal((nb, KV, bs, hd)).astype(np.float32)
    v_cache = rng.standard_normal((nb, KV, bs, hd)).astype(np.float32)
    if poison_trash:
        k_cache[0] = np.nan
        v_cache[0] = np.nan
    tables = np.zeros((len(rows), W), np.int32)
    nxt = 1
    for r, (ql, cl, al) in enumerate(rows):
        for w in range((cl + bs - 1) // bs):
            tables[r, w] = nxt
            nxt += 1
        if poison_tails and cl % bs and cl > 0:
            blk = tables[r, cl // bs]
            k_cache[blk, :, cl % bs:] = np.nan
            v_cache[blk, :, cl % bs:] = np.nan
    return (q, k_cache, v_cache, tables,
            np.asarray(q_start, np.int32),
            np.asarray([r[0] for r in rows], np.int32),
            np.asarray([r[1] for r in rows], np.int32), bs, q_tile)


def _run(case, max_q_len=None, kv_tile=0):
    q, k, v, tables, q_start, q_len, ctx_len, bs, q_tile = case
    if max_q_len is None:
        max_q_len = int(np.max(np.diff(q_start)))
    out = paged_attention_ragged(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables),
        jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(ctx_len),
        block_size=bs, max_q_len=max_q_len, q_tile=q_tile, kv_tile=kv_tile,
        interpret=True,
    )
    return np.asarray(out)


def _check(case, tol=2e-3, kv_tile=0):
    q, k, v, tables, q_start, q_len, ctx_len, bs, _ = case
    out = _run(case, kv_tile=kv_tile)
    assert np.isfinite(out).all(), "kernel leaked NaN/inf"
    ref = _reference(np.nan_to_num(q),
                     np.nan_to_num(k), np.nan_to_num(v),
                     tables, q_start, q_len, ctx_len, bs)
    err = np.max(np.abs(out - ref))
    assert err <= tol, f"max abs err {err}"
    return out


@pytest.mark.parametrize("pages", [0, 1, 2, 8])
def test_mixed_ragged_batch(pages):
    # one launch over every serving shape class: a decode row, a spec
    # verify window, a fresh prefill chunk (ctx == q_len), a continuation
    # chunk with history, and a dead seat — at the default tile (0) and at
    # 1, 2 and 8 pages a step of the KV walk
    rows = [
        (1, 37, 1),    # decode, partial last block
        (4, 20, 1),    # spec window [k+1] with history
        (8, 8, 2),     # fresh prefill chunk
        (0, 0, 1),     # dead / freshly-reset seat
        (6, 50, 2),    # continuation chunk, partial tile tail
    ]
    case = _make_case(rows)
    out = _check(case, kv_tile=pages * 16)
    # every slot of the dead row comes back exactly zero
    q_start = case[4]
    assert np.all(out[q_start[3]:q_start[4]] == 0.0)


@pytest.mark.parametrize("G", [1, 2, 4])
def test_gqa_group_sizes(G):
    rows = [(1, 17, 1), (4, 4, 1), (5, 33, 2)]
    _check(_make_case(rows, G=G, KV=2, seed=G))


def test_partial_last_blocks():
    # every ctx_len lands mid-block; poisoned tails must not leak
    rows = [(1, 1, 1), (1, 15, 1), (3, 19, 1), (7, 31, 2)]
    _check(_make_case(rows, bs=16, seed=3))


def test_all_trash_rows():
    # regression for the trash-block contract: a whole batch of
    # freshly-reset seats (q_len == 0, tables all 0, block 0 NaN) must
    # emit exact zeros and never NaN-poison the online softmax
    rows = [(0, 0, 1)] * 4
    case = _make_case(rows, seed=4)
    out = _run(case)
    assert np.all(out == 0.0)


def test_stale_table_tails_beyond_ctx():
    # seat churn: table entries past ctx_len point at recycled blocks
    # holding other sequences' (here: poisoned) data — invisible by mask
    case = _make_case([(1, 20, 1), (4, 10, 1)], seed=5)
    q, k, v, tables, q_start, q_len, ctx_len, bs, q_tile = case
    stale = np.array(tables)
    nb = k.shape[0]
    for r in range(stale.shape[0]):
        used = (int(ctx_len[r]) + bs - 1) // bs
        stale[r, used:] = nb - 1
    k[nb - 1] = np.nan
    v[nb - 1] = np.nan
    out = _run((q, k, v, stale, q_start, q_len, ctx_len, bs, q_tile))
    assert np.isfinite(out).all()
    ref = _reference(q, np.nan_to_num(k), np.nan_to_num(v), stale,
                     q_start, q_len, ctx_len, bs)
    assert np.max(np.abs(out - ref)) <= 2e-3


def test_q_tile_variants_agree():
    # same batch, different static tilings → identical numerics
    rows = [(8, 24, 1), (3, 40, 1), (8, 8, 1)]
    outs = []
    for q_tile in (1, 2, 4, 8):
        case = _make_case(rows, q_tile=8, seed=6)
        q, k, v, tables, q_start, q_len, ctx_len, bs, _ = case
        outs.append(_run((q, k, v, tables, q_start, q_len, ctx_len, bs,
                          q_tile)))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5)


def test_decode_wrapper_matches_ragged():
    # paged_attention_decode is the q_tile=1 face of the ragged kernel
    rng = np.random.default_rng(7)
    B, KV, G, hd, bs, W = 4, 2, 2, 32, 16, 4
    H = KV * G
    nb = 1 + B * W
    q = rng.standard_normal((B, H, hd)).astype(np.float32)
    k = rng.standard_normal((nb, KV, bs, hd)).astype(np.float32)
    v = rng.standard_normal((nb, KV, bs, hd)).astype(np.float32)
    tables = 1 + np.arange(B * W, dtype=np.int32).reshape(B, W)
    lens = np.asarray([1, 17, 0, 64], np.int32)
    out = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(tables), jnp.asarray(lens),
        block_size=bs, interpret=True,
    )
    out = np.asarray(out)
    assert np.all(out[2] == 0.0)
    ref = _reference(
        q, k, v, tables,
        np.arange(B + 1, dtype=np.int32),
        (lens > 0).astype(np.int32), lens, bs,
    )
    assert np.max(np.abs(out - ref)) <= 2e-3


# ---------------------------------------------------------------------------
# the KV walk follows the tokens attended (PR 25): its length is a run-time
# scalar, its step several pages, and the table's width is no part of it


def _poison_beyond_contexts(case, W):
    """The same case under a table ``W`` columns wide whose every entry
    past a row's context is a stale id of a NaN-filled block."""
    q, k, v, tables, q_start, q_len, ctx_len, bs, q_tile = case
    k, v = np.array(k), np.array(v)
    stale = k.shape[0] - 1
    k[stale] = np.nan
    v[stale] = np.nan
    wide = np.full((tables.shape[0], W), stale, np.int32)
    for r in range(tables.shape[0]):
        used = (int(ctx_len[r]) + bs - 1) // bs
        wide[r, :used] = tables[r, :used]
    return (q, k, v, wide, q_start, q_len, ctx_len, bs, q_tile)


@pytest.mark.parametrize("pages", [1, 8])
def test_result_does_not_depend_on_table_width(pages):
    # the same contexts under W = 4 and W = 512: bit-identical, and right
    rows = [(1, 37, 1), (0, 0, 1), (4, 20, 1), (1, 64, 1), (6, 50, 2)]
    case = _make_case(rows, W=4, seed=11)
    narrow = _poison_beyond_contexts(case, 4)
    wide = _poison_beyond_contexts(case, 512)
    out_n = _run(narrow, kv_tile=pages * 16)
    out_w = _run(wide, kv_tile=pages * 16)
    assert np.isfinite(out_w).all()
    np.testing.assert_array_equal(out_n, out_w)
    q, k, v, tables, q_start, q_len, ctx_len, bs, _ = wide
    ref = _reference(q, np.nan_to_num(k), np.nan_to_num(v), tables,
                     q_start, q_len, ctx_len, bs)
    assert np.max(np.abs(out_w - ref)) <= 2e-3


@pytest.mark.parametrize("pages", [1, 2, 8])
@pytest.mark.parametrize("where", [
    "one_token", "one_tile", "one_over_a_tile", "partial_last_page",
    "whole_table"])
def test_walk_ends_at_each_context(pages, where):
    bs, W = 16, 16
    tile = pages * bs
    ctx = {"one_token": 1, "one_tile": tile, "one_over_a_tile": tile + 1,
           "partial_last_page": 2 * tile + bs + 5,
           "whole_table": W * bs}[where]
    ctx = min(ctx, W * bs)
    # the context under test between a short and a long neighbour, so the
    # tile fetched ahead for the next row is started at every kind of end
    rows = [(1, 3, 1), (1, ctx, 1), (1, 90, 1), (4, max(ctx, 4), 1)]
    _check(_poison_beyond_contexts(
        _make_case(rows, W=W, seed=pages), W), kv_tile=tile)


@pytest.mark.parametrize("dead", [
    "first", "last", "between", "runs", "all_but_one"])
def test_dead_rows_anywhere(dead):
    # a dead row walks nothing but still hands the next row's first tile on
    live = [(1, 37, 1), (4, 20, 1), (1, 130, 1)]
    d = (0, 0, 1)
    rows = {"first": [d] + live, "last": live + [d],
            "between": [live[0], d, live[1], d, live[2]],
            "runs": [d, d] + live[:1] + [d, d, d] + live[1:] + [d, d],
            "all_but_one": [d, d, d, live[2], d, d]}[dead]
    case = _poison_beyond_contexts(_make_case(rows, W=16, seed=12), 16)
    out = _check(case, kv_tile=32)
    q_start, q_len = case[4], case[5]
    for r in range(len(rows)):
        if q_len[r] == 0:
            assert np.all(out[q_start[r]:q_start[r + 1]] == 0.0)


@pytest.mark.parametrize("pages", [1, 2, 8])
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_scale_planes_walk(kv_dtype, pages):
    # int8 / fp8 pages with their per-(slot, head) scale planes, NaN scales
    # in the trash block and in every partial tail, decode rows and a
    # prefill chunk, against the naive softmax on the dequantized caches
    from dynamo_tpu.engine import attention_parity as parity
    from dynamo_tpu.engine import quant
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig

    mc = ModelConfig.tiny()
    ec = EngineConfig(block_size=16, num_blocks=128, max_num_seqs=8,
                      max_num_batched_tokens=256, max_model_len=256,
                      decode_buckets=(8,), prefill_buckets=(16, 32),
                      kv_dtype=kv_dtype)
    for attn_class, B, T in (("decode", 5, 1), ("prefill", 3, 16)):
        case = parity.make_sweep_case(mc, ec, attn_class, B, T, W=12,
                                      ctx=150, seed=pages)
        q, kc, vc, tables, q_start, q_len, ctx_len = case["args"]
        out = np.asarray(paged_attention_ragged(
            *(jnp.asarray(a) for a in case["args"]),
            block_size=16, max_q_len=T, kv_tile=pages * 16, interpret=True,
            k_scale=jnp.asarray(case["k_scale"]),
            v_scale=jnp.asarray(case["v_scale"]),
        )).astype(np.float64)
        assert np.isfinite(out).all(), "kernel leaked NaN/inf"
        ref = parity.reference_naive(
            q, quant.kv_dequantize_cache_np(kc, case["k_scale"]),
            quant.kv_dequantize_cache_np(vc, case["v_scale"]),
            tables, q_start, q_len, ctx_len, block_size=16)
        mask = parity.valid_slot_mask(q_start, q_len, out.shape[0])
        assert np.max(np.abs(out[mask] - ref[mask])) <= 2e-3
        assert np.all(out[~mask] == 0.0)


@pytest.mark.parametrize("bs,kv_heads,hd,dtype,want", [
    # the benchmark's shapes: 16 pages of 16 = 256 keys, two lane-width
    # score tiles, on one chip (8 KV heads) and on the tp4 shard (2)
    (16, 8, 128, jnp.bfloat16, 256),
    (16, 2, 128, jnp.bfloat16, 256),
    # int8 pages: narrower slots, the same float32 working copies
    (16, 8, 64, jnp.int8, 256),
    # a head narrower than the lanes is budgeted as a whole lane tile
    (4, 2, 16, jnp.float32, 256),
    # a step too fat for its share of scoped VMEM takes half the pages ...
    (16, 16, 128, jnp.bfloat16, 128),
    # ... and never less than one: a page is not split by default, however
    # fat, and a page larger than the aim is one step
    (16, 64, 256, jnp.float32, 16),
    (512, 8, 128, jnp.bfloat16, 512),
])
def test_default_tile_follows_the_shapes_the_kernel_sees(
        bs, kv_heads, hd, dtype, want):
    from dynamo_tpu.ops.paged_attention import default_kv_tile

    assert default_kv_tile(bs, kv_heads, hd, dtype) == want
