"""``model._kv_write`` against the plain expression it replaced.

The step programs write a chunk's K/V (and, for a quantized cache, the scale
planes) through a ``[NB*KV*bs, ...]`` view so that XLA's scatter keeps the
cache row-major, as the Pallas kernel reads it (``engine/model.py`` header).
The plain ``plane.at[block, :, off].set(upd)`` stays here as the reference:
the cache must come out bit-equal — every dtype, T == 1 and T > 1, pads and
duplicate writes into trash block 0, one device and a ``tp`` mesh.
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


def _reference_write(plane, blocks, offs, upd, mesh=None):
    return plane.at[blocks, :, offs].set(upd)


def _bits(x) -> np.ndarray:
    a = np.asarray(x)
    return a.view(np.uint8 if a.dtype.itemsize == 1 else f"u{a.dtype.itemsize}")


def _slots(T: int, rs: np.random.RandomState):
    """(block, offset) per (row, token) the way ``forward`` derives them:
    a per-row prefix of valid positions, -1 pads to trash block 0."""
    tables = 1 + rs.permutation(NB - 1)[:B * W].reshape(B, W)
    start = rs.randint(0, W * BS - T, size=B)
    n_valid = rs.randint(1, T + 1, size=B)
    n_valid[0] = max(0, T - 2)          # at least two pads when T > 2
    n_valid[1] = 0                      # a dead row: every write is a pad
    pos = np.where(np.arange(T)[None, :] < n_valid[:, None],
                   start[:, None] + np.arange(T)[None, :], -1)
    safe = np.maximum(pos, 0)
    block = np.where(pos >= 0,
                     np.take_along_axis(tables, safe // BS, axis=1), 0)
    off = np.where(pos >= 0, safe % BS, 0)
    return (block.reshape(-1).astype(np.int32),
            off.reshape(-1).astype(np.int32))


def _planes_and_updates(kv_dtype: str, n: int, rs: np.random.RandomState):
    """[(plane, update)]: the payload plane, and for a quantized cache the
    scale plane too; planes are full of noise so a stray write shows."""
    upd = jnp.asarray(rs.randn(n, KV, HD), jnp.bfloat16)
    old = jnp.asarray(rs.randn(NB, KV, BS, HD), jnp.bfloat16)
    if not quant.is_quantized(kv_dtype):
        return [(old, upd)]
    q_upd, s_upd = quant.kv_quantize(upd, kv_dtype)
    q_old, s_old = quant.kv_quantize_cache_np(np.asarray(old, np.float32),
                                              kv_dtype)
    return [(jnp.asarray(q_old), q_upd), (jnp.asarray(s_old), s_upd)]


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("T", [1, 6])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_kv_write_is_bit_equal_to_the_plain_scatter(
        cpu_devices, kv_dtype, T, tp):
    rs = np.random.RandomState(100 * T + tp)
    blocks, offs = _slots(T, rs)
    pads = blocks == 0
    assert pads.sum() >= 2 and (~pads).any()
    mesh = make_mesh((1, tp), devices=cpu_devices[:tp]) if tp > 1 else None
    for plane, upd in _planes_and_updates(kv_dtype, B * T, rs):
        want = jax.jit(_reference_write)(plane, blocks, offs, upd)
        fn = jax.jit(lambda p, b, o, u: model_lib._kv_write(p, b, o, u, mesh))
        if mesh is not None:
            lay = SpecLayout.for_mesh(mesh)
            spec = (lay.cache_block() if plane.ndim == 4
                    else lay.cache_scale_block())
            plane = jax.device_put(
                plane, jax.sharding.NamedSharding(mesh, spec))
        got = fn(plane, blocks, offs, upd)
        assert got.dtype == want.dtype and got.shape == want.shape
        if mesh is not None:
            assert got.sharding.is_equivalent_to(plane.sharding, plane.ndim)
        got_b, want_b, old_b = _bits(got), _bits(want), _bits(plane)
        # every real block: the same bits as the plain expression's
        np.testing.assert_array_equal(got_b[1:], want_b[1:])
        assert (got_b[1:] != old_b[1:]).any()
        # trash block 0: only slot 0 is written, and (duplicates may race)
        # each head holds one of the pads' rows
        np.testing.assert_array_equal(got_b[0, :, 1:], old_b[0, :, 1:])
        pad_rows = _bits(upd)[pads]                       # [P, KV, ...]
        for h in range(KV):
            assert any(np.array_equal(got_b[0, h, 0], r[h])
                       for r in pad_rows)


@pytest.mark.parametrize("T", [1, 8])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_forward_leaves_the_cache_the_plain_scatter_left(
        monkeypatch, kv_dtype, T):
    """The wiring: ``forward`` hands each plane its own update, and the
    cache it returns is, bit for bit, the one the old expression built."""
    cfg = ModelConfig.tiny()
    eng = EngineConfig(block_size=4, num_blocks=32, max_num_seqs=4,
                       max_num_batched_tokens=32, max_model_len=64,
                       attention_impl="einsum", kv_dtype=kv_dtype)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(T)
    tokens = rs.randint(1, cfg.vocab_size, size=(3, T)).astype(np.int32)
    n_valid = np.array([T, max(1, T - 3), 0])
    pos = np.where(np.arange(T)[None, :] < n_valid[:, None],
                   np.array([[5], [0], [0]]) + np.arange(T)[None, :], -1)
    tables = (1 + np.arange(3 * 4)).reshape(3, 4).astype(np.int32)

    def run():
        cache, h = jax.jit(
            lambda p, c: model_lib.forward(
                cfg, eng, p, c, tokens, pos.astype(np.int32), tables)
        )(params, model_lib.init_cache(cfg, eng))
        return cache, h

    got, h_got = run()
    monkeypatch.setattr(model_lib, "_kv_write", _reference_write)
    want, h_want = run()
    assert sorted(got) == sorted(want)
    assert ("ks" in got) == quant.is_quantized(kv_dtype)
    for key in want:
        for g, w in zip(got[key], want[key]):
            np.testing.assert_array_equal(_bits(g)[1:], _bits(w)[1:])
            assert np.asarray(w[1:].astype(jnp.float32)).any()
    np.testing.assert_array_equal(_bits(h_got)[:2], _bits(h_want)[:2])
