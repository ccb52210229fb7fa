"""``model._kv_write`` against the plain expression it replaced.

The step programs write a chunk's K/V (and, for a quantized cache, the scale
planes) whole pages at a time: read the pages a row touches, lay the new rows
over them, scatter the merged pages back on the leading dim, so that XLA's
scatter keeps the cache row-major, as the Pallas kernel reads it, and carries
as many updates as pages (``engine/model.py`` header). The plain
``plane.at[block, :, off].set(upd)`` stays here as the reference: every real
block must come out bit-equal — every dtype, T == 1 and T > 1, pads and dead
rows, one device and a ``tp`` mesh — and block 0, where pads and dead rows
point, must hold the bytes it had.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as model_lib
from dynamo_tpu.engine import quant
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.parallel.layout import SpecLayout, make_mesh

NB, KV, BS, HD = 24, 4, 4, 16
B, W = 4, 5


def _reference_write(plane, pages, shift, mask, upd, mesh=None, *,
                     positions, tables):
    """The plain scatter, one (block, offset) per (row, token) the way
    ``forward`` derived them before the page form: pads to block 0."""
    pos = np.asarray(positions)
    safe = np.maximum(pos, 0)
    width = tables.shape[1]
    block = np.where(pos >= 0, np.take_along_axis(
        np.asarray(tables), np.minimum(safe // BS, width - 1), axis=1), 0)
    off = np.where(pos >= 0, safe % BS, 0)
    return plane.at[block.reshape(-1), :, off.reshape(-1)].set(
        upd.reshape((-1,) + upd.shape[2:]))


def _bits(x) -> np.ndarray:
    a = np.asarray(x)
    return a.view(np.uint8 if a.dtype.itemsize == 1 else f"u{a.dtype.itemsize}")


def _feed(start, n_valid, T):
    """positions [B, T] under ``forward``'s contract: a per-row prefix of
    ``n_valid`` contiguous positions from ``start``, -1 pads."""
    start, n_valid = np.asarray(start), np.asarray(n_valid)
    return np.where(np.arange(T)[None, :] < n_valid[:, None],
                    start[:, None] + np.arange(T)[None, :], -1
                    ).astype(np.int32)


def _random_feed(T: int, rs: np.random.RandomState):
    tables = (1 + rs.permutation(NB - 1)[:B * W].reshape(B, W)
              ).astype(np.int32)
    start = rs.randint(0, W * BS - T, size=B)
    n_valid = rs.randint(1, T + 1, size=B)
    n_valid[0] = max(0, T - 2)          # at least two pads when T > 2
    n_valid[1] = 0                      # a dead row: every write is a pad
    return _feed(start, n_valid, T), tables


def _planes_and_updates(kv_dtype: str, rows: int, T: int,
                        rs: np.random.RandomState):
    """[(plane, update)]: the payload plane, and for a quantized cache the
    scale plane too; planes are full of noise so a stray write shows."""
    upd = jnp.asarray(rs.randn(rows, T, KV, HD), jnp.bfloat16)
    old = jnp.asarray(rs.randn(NB, KV, BS, HD), jnp.bfloat16)
    if not quant.is_quantized(kv_dtype):
        return [(old, upd)]
    q_upd, s_upd = quant.kv_quantize(upd, kv_dtype)
    q_old, s_old = quant.kv_quantize_cache_np(np.asarray(old, np.float32),
                                              kv_dtype)
    return [(jnp.asarray(q_old), q_upd), (jnp.asarray(s_old), s_upd)]


def _check(plane, upd, positions, tables, mesh=None):
    """One plane through ``_kv_write`` and through the plain scatter: real
    blocks bit-equal, block 0 untouched."""
    want = _reference_write(plane, None, None, None, upd,
                            positions=positions, tables=tables)

    def write(p, pos, tb, u):
        return model_lib._kv_write(
            p, *model_lib._kv_pages(pos, tb, BS), u, mesh)

    if mesh is not None:
        lay = SpecLayout.for_mesh(mesh)
        spec = (lay.cache_block() if plane.ndim == 4
                else lay.cache_scale_block())
        plane = jax.device_put(
            plane, jax.sharding.NamedSharding(mesh, spec))
    got = jax.jit(write)(plane, positions, tables, upd)
    assert got.dtype == want.dtype and got.shape == want.shape
    if mesh is not None:
        assert got.sharding.is_equivalent_to(plane.sharding, plane.ndim)
    got_b, want_b, old_b = _bits(got), _bits(want), _bits(plane)
    # every real block: the same bits as the plain expression's
    np.testing.assert_array_equal(got_b[1:], want_b[1:])
    # block 0 holds the bytes it had: a redirected entry writes them back
    np.testing.assert_array_equal(got_b[0], old_b[0])
    return got_b, old_b


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("T", [1, 6])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_kv_write_is_bit_equal_to_the_plain_scatter(
        cpu_devices, kv_dtype, T, tp):
    rs = np.random.RandomState(100 * T + tp)
    positions, tables = _random_feed(T, rs)
    assert (positions < 0).sum() >= 2 and (positions >= 0).any()
    mesh = make_mesh((1, tp), devices=cpu_devices[:tp]) if tp > 1 else None
    for plane, upd in _planes_and_updates(kv_dtype, B, T, rs):
        got_b, old_b = _check(plane, upd, positions, tables, mesh)
        assert (got_b[1:] != old_b[1:]).any()


# what the page form can get wrong and the row form could not: each case is
# (T, start per row, valid tokens per row, real pages in each row's table)
_PAGE_CASES = {
    # a start that is not page-aligned, a chunk that crosses 3 boundaries
    "unaligned_start_crosses_pages": (11, [3, 6, 1], [11, 9, 10], W),
    # the chunk's last token is a page's last slot
    "ends_on_a_page_end": (6, [2, 6, 0], [6, 6, 4], W),
    # dead rows and full rows side by side
    "n_zero_and_n_T": (6, [5, 0, 9, 3], [0, 6, 6, 0], W),
    # T not a multiple of bs, starting on the last slot of a page
    "T_not_a_multiple_of_bs": (7, [3, 7, 11], [7, 5, 7], W),
    # the table holds fewer real pages than P = (T + bs - 2) // bs + 1 = 3:
    # entries past the row's last page are 0 and must not be written
    "table_shorter_than_P": (9, [1, 0, 2], [6, 8, 5], 2),
    # decode rows at every slot of a page, some dead
    "decode_every_slot": (1, [4, 5, 6, 7, 8, 0], [1, 1, 0, 1, 1, 0], 3),
    # one token in the first page and the rest behind it
    "one_token_in_the_first_page": (8, [3, 7], [8, 2], W),
}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(_PAGE_CASES))
def test_kv_write_page_form_edge_cases(kv_dtype, case):
    T, start, n_valid, real_pages = _PAGE_CASES[case]
    rows = len(start)
    rs = np.random.RandomState(len(case))
    tables = np.zeros((rows, W), np.int32)
    tables[:, :real_pages] = (
        1 + rs.permutation(NB - 1)[:rows * real_pages]
    ).reshape(rows, real_pages)
    positions = _feed(start, n_valid, T)
    assert positions.max() < real_pages * BS
    for plane, upd in _planes_and_updates(kv_dtype, rows, T, rs):
        got_b, old_b = _check(plane, upd, positions, tables)
        # only slots that took a token changed: count them
        changed = (got_b != old_b).reshape(NB, KV, BS, -1).any(axis=(1, 3))
        assert changed.sum() == sum(n_valid)


def test_kv_pages_counts_the_pages_a_row_touches():
    # P = (T + bs - 2) // bs + 1; live entries are the row's own pages in
    # order, everything else is block 0
    T = 9
    tables = (1 + np.arange(3 * W)).reshape(3, W).astype(np.int32)
    positions = _feed([3, 8, 0], [9, 2, 0], T)
    pages, shift, mask = model_lib._kv_pages(
        jnp.asarray(positions), jnp.asarray(tables), BS)
    assert pages.shape == (3, 3) and mask.shape == (3, 3 * BS)
    np.testing.assert_array_equal(np.asarray(shift), [3, 0, 0])
    np.testing.assert_array_equal(
        np.asarray(pages),
        [[tables[0, 0], tables[0, 1], tables[0, 2]],
         [tables[1, 2], 0, 0],
         [0, 0, 0]])
    np.testing.assert_array_equal(np.asarray(mask).sum(axis=1), [9, 2, 0])
    assert np.asarray(mask)[0, 3:12].all()


def test_check_feed_positions_holds_a_feed_to_the_contract():
    ok = _feed([5, 0, 7], [4, 0, 1], 4)
    model_lib.check_feed_positions(ok)
    for bad in ([[5, 7, -1, -1]],           # a gap
                [[-1, 5, 6, -1]],           # valid tokens not a prefix
                [[5, 6, -1, 8]]):           # a pad inside the row
        with pytest.raises(AssertionError):
            model_lib.check_feed_positions(np.asarray(bad, np.int32))


@pytest.mark.parametrize("T", [1, 8])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_forward_leaves_the_cache_the_plain_scatter_left(
        monkeypatch, kv_dtype, T):
    """The wiring: ``forward`` hands each plane its own update, and the
    cache it returns is, bit for bit on every real block, the one the old
    expression built; block 0 stays as ``init_cache`` made it."""
    cfg = ModelConfig.tiny()
    eng = EngineConfig(block_size=4, num_blocks=32, max_num_seqs=4,
                       max_num_batched_tokens=32, max_model_len=64,
                       attention_impl="einsum", kv_dtype=kv_dtype)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(T)
    tokens = rs.randint(1, cfg.vocab_size, size=(3, T)).astype(np.int32)
    pos = _feed([5, 0, 0], [T, max(1, T - 3), 0], T)
    tables = (1 + np.arange(3 * 4)).reshape(3, 4).astype(np.int32)

    def run():
        cache, h = jax.jit(
            lambda p, c: model_lib.forward(
                cfg, eng, p, c, tokens, pos, tables)
        )(params, model_lib.init_cache(cfg, eng))
        return cache, h

    got, h_got = run()
    monkeypatch.setattr(
        model_lib, "_kv_write",
        lambda *a, **k: _reference_write(*a, **k, positions=pos,
                                         tables=tables))
    want, h_want = run()
    assert sorted(got) == sorted(want)
    assert ("ks" in got) == quant.is_quantized(kv_dtype)
    for key in want:
        for g, w in zip(got[key], want[key]):
            np.testing.assert_array_equal(_bits(g)[1:], _bits(w)[1:])
            assert np.asarray(w[1:].astype(jnp.float32)).any()
            assert not _bits(g)[0].any()        # block 0: still zeros
    np.testing.assert_array_equal(_bits(h_got)[:2], _bits(h_want)[:2])
