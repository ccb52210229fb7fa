"""Pallas paged-attention decode kernel vs the einsum reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as model_lib
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.ops.paged_attention import paged_attention_decode


def _reference(q, k_cache, v_cache, tables, seq_lens, bs):
    """Dense attention over the gathered paged context (float64-ish ref).

    Caches are block-major: [num_blocks, KV, bs, hd]."""
    B, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    out = np.zeros((B, H, hd), np.float32)
    for b in range(B):
        n = int(seq_lens[b])
        if n == 0:
            continue
        k = np.stack([
            np.asarray(k_cache, np.float32)[tables[b, pos // bs], :,
                                            pos % bs]
            for pos in range(n)
        ])                                            # [n, KV, hd]
        v = np.stack([
            np.asarray(v_cache, np.float32)[tables[b, pos // bs], :,
                                            pos % bs]
            for pos in range(n)
        ])
        for h in range(H):
            kv = h // G
            s = (np.asarray(q, np.float32)[b, h] @ k[:, kv].T) / np.sqrt(hd)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[b, h] = p @ v[:, kv]
    return out


@pytest.mark.parametrize("kv_tile", [0, 8, 16, 64])
@pytest.mark.parametrize("seq_lens", [
    [7, 33, 0, 16], [1, 1, 1, 1], [0, 64, 0, 63], [0, 0, 0, 9]])
def test_decode_kernel_matches_dense(seq_lens, kv_tile):
    # kv_tile: the default, then 1, 2 and 8 pages of 8 a step of the walk
    bs, W, B = 8, 8, 4
    KV, G, hd = 2, 4, 16
    H = KV * G
    num_blocks = 1 + B * W
    rng = np.random.default_rng(0)

    q = rng.standard_normal((B, H, hd), dtype=np.float32)
    k_cache = rng.standard_normal((num_blocks, KV, bs, hd), dtype=np.float32)
    v_cache = rng.standard_normal((num_blocks, KV, bs, hd), dtype=np.float32)
    # distinct physical blocks per row; block 0 is the trash block
    tables = np.zeros((B, W), np.int32)
    for b in range(B):
        tables[b] = 1 + b * W + np.arange(W)
    seq_lens = np.asarray(seq_lens, np.int32)

    got = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(tables), jnp.asarray(seq_lens),
        block_size=bs, kv_tile=kv_tile, interpret=True,
    )
    want = _reference(q, k_cache, v_cache, tables, seq_lens, bs)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_full_decode_step_pallas_vs_einsum():
    """End-to-end: one decode step through forward() with both impls."""
    cfg = ModelConfig.tiny()
    rng = jax.random.PRNGKey(0)
    params = model_lib.init_params(rng, cfg)

    results = {}
    for impl in ("einsum", "pallas"):
        eng = EngineConfig(
            num_blocks=32, max_model_len=256, attention_impl=impl,
        )
        cache = model_lib.init_cache(cfg, eng)
        # prefill 20 tokens into blocks 1,2 (einsum path, T>1)
        T = 20
        tokens = np.arange(1, T + 1, dtype=np.int32)[None, :]
        positions = np.arange(T, dtype=np.int32)[None, :]
        tables = np.zeros((1, 16), np.int32)
        tables[0, :2] = [1, 2]
        cache, _ = model_lib.forward(
            cfg, eng, params, cache,
            jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(tables),
        )
        # decode one token at position 20
        dt = np.array([[7]], np.int32)
        dp = np.array([[T]], np.int32)
        cache, h = model_lib.forward(
            cfg, eng, params, cache,
            jnp.asarray(dt), jnp.asarray(dp), jnp.asarray(tables),
        )
        results[impl] = np.asarray(h[0, 0], np.float32)

    np.testing.assert_allclose(
        results["pallas"], results["einsum"], rtol=2e-4, atol=2e-4
    )
