"""``model._qkv_proj``: the q / k / v products behind an optimization barrier.

The barrier keeps XLA's TPU pipeline from folding the head reshape into the
dot, which made every step program transpose ``wq`` and ``wk`` before
multiplying by them (PERF.md, PR 31; what the chip's compiler does with and
without it is held in ``tests/test_chip_compile.py``). Here, on the CPU: it
changes no value anywhere it is used, and it is in every layer of the
programs that should have it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request


def _engine_config(**kw):
    return EngineConfig(block_size=4, num_blocks=64, max_num_seqs=4,
                        max_num_batched_tokens=64, max_model_len=128,
                        decode_buckets=(4, 8), prefill_buckets=(16,), **kw)


def _without_the_barrier(monkeypatch):
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)


def _chunk(cfg, eng, B, T):
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (B, T)),
        jnp.int32)
    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32), (B, 1))
    nb = -(-T // eng.block_size)
    tables = np.zeros((B, eng.max_blocks_per_seq), np.int32)
    for b in range(B):
        tables[b, :nb] = 1 + b * nb + np.arange(nb)
    return tokens, positions, jnp.asarray(tables)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4)])
@pytest.mark.parametrize("wd", ["bf16", "int8"])
def test_the_products_are_the_plain_matmuls_split_into_heads(wd, mesh_shape):
    cfg = ModelConfig.tiny()
    mesh = M.make_mesh(mesh_shape, jax.devices()[:mesh_shape[1]])
    params = M.init_params_sharded(jax.random.PRNGKey(3), cfg, mesh, wd)
    B, T = 2, 8
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, cfg.hidden_size),
                          jnp.dtype(cfg.dtype))

    def behind_the_barrier(layers, x):
        return M._qkv_proj(x, M._LayerSlice(layers, 1), H, KV, hd)

    def written_out(layers, x):
        p = M._LayerSlice(layers, 1)
        return (M._mm(x, p["wq"]).reshape(B, T, H, hd),
                M._mm(x, p["wk"]).reshape(B, T, KV, hd),
                M._mm(x, p["wv"]).reshape(B, T, KV, hd))

    got = jax.jit(behind_the_barrier)(params["layers"], x)
    want = jax.jit(written_out)(params["layers"], x)
    assert [g.shape for g in got] == [
        (B, T, H, hd), (B, T, KV, hd), (B, T, KV, hd)]
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("T", [1, 16])
@pytest.mark.parametrize("kind", ["bf16", "int8", "moe"])
def test_forward_computes_the_same_bits_with_and_without_it(
        kind, T, monkeypatch):
    cfg = ModelConfig.tiny_moe() if kind == "moe" else ModelConfig.tiny()
    wd = "int8" if kind == "int8" else "bf16"
    eng = _engine_config(weight_dtype=wd, attention_impl="einsum")
    mesh = M.make_mesh((1, 1), jax.devices()[:1])
    params = M.init_params_sharded(jax.random.PRNGKey(1), cfg, mesh, wd)
    tokens, positions, tables = _chunk(cfg, eng, 2, T)

    def run():
        cache = M.init_cache_sharded(cfg, eng, mesh)
        cache, h = jax.jit(
            lambda p, c: M.forward(cfg, eng, p, c, tokens, positions,
                                   tables, mesh=mesh))(params, cache)
        return (np.asarray(M.logits_fn(cfg, params, h), np.float32),
                [np.asarray(k) for k in cache["k"]])

    logits, pages = run()
    _without_the_barrier(monkeypatch)
    plain_logits, plain_pages = run()
    assert np.array_equal(logits, plain_logits)
    for a, b in zip(pages, plain_pages):
        assert np.array_equal(a, b)


def test_encode_forward_computes_the_same_bits_with_and_without_it(
        monkeypatch):
    cfg = ModelConfig.tiny()
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    tokens, positions, _ = _chunk(cfg, _engine_config(), 2, 12)

    def run():
        return np.asarray(jax.jit(
            lambda p: M.encode_forward(cfg, p, tokens, positions))(params),
            np.float32)

    got = run()
    _without_the_barrier(monkeypatch)
    assert np.array_equal(got, run())


@pytest.mark.parametrize("program", ["forward", "encode_forward"])
def test_every_layer_of_a_program_has_its_barrier(program):
    cfg = ModelConfig.tiny()
    eng = _engine_config(attention_impl="einsum")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens, positions, tables = _chunk(cfg, eng, 2, 4)
    if program == "forward":
        cache = M.init_cache(cfg, eng)
        text = jax.jit(
            lambda p, c: M.forward(cfg, eng, p, c, tokens, positions,
                                   tables)).lower(params, cache).as_text()
    else:
        text = jax.jit(
            lambda p: M.encode_forward(cfg, p, tokens, positions)
        ).lower(params).as_text()
    assert text.count("optimization_barrier") == cfg.num_layers


@pytest.mark.anyio
@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4)])
async def test_an_engine_serves_the_same_tokens_with_and_without_it(
        mesh_shape, monkeypatch):
    """The step programs as the engine jits them (``in_shardings`` on the
    mesh, the Pallas decode path) decode what the unbarriered ones do."""
    cfg = ModelConfig.tiny()
    eng = _engine_config(mesh_shape=mesh_shape)

    async def tokens_of(engine):
        await engine.start()
        try:
            req = Request(request_id="qkv", token_ids=list(range(5, 25)),
                          max_tokens=6, temperature=0.0, ignore_eos=True)
            return [o.token_id for o in [o async for o in
                                         engine.submit(req)]]
        finally:
            await engine.stop()

    served = await tokens_of(InferenceEngine(cfg, eng, seed=2))
    _without_the_barrier(monkeypatch)
    assert await tokens_of(InferenceEngine(cfg, eng, seed=2)) == served
    assert len(served) == 6
