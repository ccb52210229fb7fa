"""A table of layer kinds (``ModelConfig.layer_types``): two attention kinds
with their own heads, window, rope and gate, a dense layer 0 and routed
experts without drops on the share of them held here — the tiny
configuration of ``benchmarks/chip/configs/laguna-s-2.1-ep2.json``
(``rehearse.model``: window 16, 4 / 6 query heads over 2 KV heads, 16 routed
experts of which 8 held, 4 a token) against ``references/laguna.py`` and
against plain numpy.  CPU, float32; Pallas kernels interpreted.

The cases that hold for any table run over ``TABLES``: since PR 34 also the
tiny configuration of ``ling-3.0-flash-ep8.json``, whose layers keep no K
and V (what only that table has is in ``test_hybrid_table.py``), and since
PR 41 that of ``longcat-flash-omni-ep32.json``: double layers of latent rows
with a shortcut expert layer and zero-compute experts
(``test_shortcut_table.py``)."""

import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request
from dynamo_tpu.observability import flops as F
from dynamo_tpu.observability.stepstats import DECODE, kv_blocks_walked
from dynamo_tpu.ops.paged_attention import (
    paged_attention_decode, paged_attention_ragged,
)
from dynamo_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a table's configuration and reference, the seed of its tests, the
# sequences ``compare`` runs at this size (laguna: 4.5 windows in chunks of
# 32; ling: chunks of 64, the last 22 tokens), its sparse layers, and what
# its engine is built with beside ``_engine_config``'s defaults (a table
# with seat state compiles every program when built: one bucket suffices)
TABLES = {
    "laguna": dict(config="laguna-s-2.1-ep2", reference="laguna",
                   seed=2500000417, compare=dict(T=72, chunk=32),
                   sparse_layers=4, engine={}),
    "ling": dict(config="ling-3.0-flash-ep8", reference="ling",
                 seed=3400000417, compare=dict(T=150, chunk=64),
                 sparse_layers=6, engine=dict(prefill_buckets=(64,))),
    "longcat": dict(config="longcat-flash-omni-ep32", reference="longcat",
                    seed=4100000417, compare=dict(T=150, chunk=64),
                    sparse_layers=2, engine={}),
}
SEED = TABLES["laguna"]["seed"]


def _file(table: str = "laguna") -> dict:
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           TABLES[table]["config"] + ".json")) as f:
        return json.load(f)


def _model(rehearse: bool = True, table: str = "laguna",
           **replace) -> ModelConfig:
    from benchmarks.chip import worker_launch as WL

    cfg = WL.model_config_from(_file(table), rehearse)
    return dataclasses.replace(cfg, **replace) if replace else cfg


def _reference(table: str = "laguna"):
    name = TABLES[table]["reference"]
    path = os.path.join(ROOT, "benchmarks", "chip", "references",
                        name + ".py")
    spec = importlib.util.spec_from_file_location(name + "_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine_config(table: str = "laguna", **kw) -> EngineConfig:
    base = dict(num_blocks=96, max_model_len=256, max_num_batched_tokens=64,
                prefill_buckets=(16, 32, 64), decode_buckets=(8,),
                max_num_seqs=8, pipeline_depth=1,
                attention_impl="pallas")
    base.update(TABLES[table]["engine"])
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def engines():
    """``engines(table)``: the table's engine, built once a module."""
    built = {}

    def get(table: str):
        if table not in built:
            built[table] = InferenceEngine(
                _model(table=table), _engine_config(table),
                seed=TABLES[table]["seed"])
        return built[table]

    return get


@pytest.fixture(scope="module")
def engine(engines):
    return engines("laguna")


# ------------------------- the table itself ---------------------------------


def test_table_reads_the_published_keys():
    cfg = _model(rehearse=False)
    assert [(k.name, k.num_heads, k.window, k.layers)
            for k in cfg.attn_kinds] == [
        ("full_attention", 48, 0, (0, 4)),
        ("sliding_attention", 72, 512, (1, 2, 3))]
    assert [(e.attn, e.attn_at, e.ffn, e.ffn_at) for e in cfg.layer_table] \
        == [(0, 0, "dense", 0), (1, 0, "sparse", 0), (1, 1, "sparse", 1),
            (1, 2, "sparse", 2), (0, 1, "sparse", 3)]
    assert cfg.experts_held == (0, 128) and cfg.num_routed_experts == 256
    assert cfg.has_routed_experts and not cfg.is_moe
    assert hash(cfg) == hash(_model(rehearse=False))   # a jit static argument
    second = dataclasses.replace(cfg, expert_shard={"index": 1, "of": 2})
    assert second.experts_held == (128, 128)


@pytest.mark.parametrize("table,change,names", [
    ("laguna", {"layer_types": ("full_attention",) * 4},
     ["layer_types", "4", "5"]),
    ("laguna", {"num_heads_per_layer": (4, 6, 6, 5, 4)},
     ["layer 3", "5", "6"]),
    ("laguna", {"num_heads_per_layer": (4, 5, 5, 5, 4)},
     ["5 query heads", "2 KV"]),
    ("laguna", {"sliding_window": 0}, ["sliding_window"]),
    ("laguna", {"attn_gate": "per-channel"}, ["per-channel"]),
    ("laguna", {"num_experts": 16}, ["num_experts 16", "16 routed"]),
    ("laguna", {"num_experts_per_token": 17}, ["num_experts_per_token"]),
    ("laguna", {"moe_intermediate_size": 0}, ["expert widths"]),
    ("laguna", {"mlp_layer_types": ("dense", "sparse", "sparse", "sparse",
                                    "mixed")}, ["mixed"]),
    ("laguna", {"rope_parameters": {"full_attention": {"rope_theta": 1e4}}},
     ["rope_parameters", "sliding_attention"]),
    ("ling", {"short_conv_kernel_size": 0}, ["short_conv_kernel_size"]),
    ("ling", {"kda_lower_bound": 0.0}, ["kda_lower_bound"]),
    ("ling", {"state_dtype": "float16"}, ["state_dtype", "float16"]),
    ("ling", {"kv_lora_rank": 0}, ["kv_lora_rank"]),
    ("ling", {"score_function": "tanh"}, ["score_function"]),
    ("ling", {"topk_group": 9}, ["topk_group"]),
    ("ling", {"n_group": 3}, ["n_group"]),
    ("ling", {"rope_parameters": {}},
     ["rope_parameters has no 'mla_attention'"]),
])
def test_a_table_that_contradicts_itself_is_refused_when_built(table, change,
                                                               names):
    with pytest.raises(ValueError) as e:
        _model(table=table, **change)
    for n in names:
        assert n in str(e.value), (n, str(e.value))


def test_parameters_are_one_stack_a_kind():
    cfg = _model()
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    lay = params["layers"]
    D, hd = cfg.hidden_size, cfg.head_dim_
    assert lay["wq"]["full_attention"].shape == (2, D, 4 * hd)
    assert lay["wq"]["sliding_attention"].shape == (3, D, 6 * hd)
    assert lay["wo"]["sliding_attention"].shape == (3, 6 * hd, D)
    assert lay["w_attn_gate"]["full_attention"].shape == (2, D, 4)
    assert lay["wk"].shape == (5, D, 2 * hd)
    assert lay["w_gate"].shape == (1, D, cfg.intermediate_size)
    assert lay["w_router"].shape == (4, D, 16)
    assert lay["shared_down"].shape == (4, 32, D)
    assert [a.shape for a in lay["expert_gate"]] == [(8, D, 32)] * 4
    assert [a.shape for a in lay["expert_down"]] == [(8, 32, D)] * 4
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == F.param_count(cfg)
    # the same key draws the same weights, and the sharded entry point (one
    # device) the same again
    again = M.init_params_sharded(jax.random.PRNGKey(1), cfg, None)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the cache stays one [NB, KV, bs, hd] pair a layer
    cache = M.init_cache(cfg, _engine_config())
    assert len(cache["k"]) == 5 and cache["k"][0].shape == (96, 2, 16, hd)


# ------------------------- against the reference ----------------------------


@pytest.mark.parametrize("table", sorted(TABLES))
def test_chunked_prefill_and_kernel_decode_match_the_reference(engines, table):
    """``forward`` in chunks into the table's caches (laguna: a paged cache,
    sequences 4.5 windows long; ling: a latent cache and two seats of a
    state pool, the last chunk ragged), then the decode path with its Pallas
    kernels interpreted: logits against the plain float32 forward.  float32
    against float32 at ``highest``: what is left is the order of the sums,
    1e-4 of the largest logit at most."""
    t = TABLES[table]
    v = _reference(table).compare(engines(table), t["seed"], n_decode=6,
                                  **t["compare"])
    assert v["ok"], v
    assert v["decode_attention"]["impl"] == "pallas"
    assert v["decode_attention"]["interpret"] is True
    assert max(v["prefill"]["rel"], v["decode"]["rel"]) < 1e-4, v
    assert v["both"]["rms_rel"] < 1e-4
    # float32 against float32: the served path chose the reference's experts
    assert v["routing"]["token_layers"] == (
        2 * (t["compare"]["T"] + 6) * t["sparse_layers"])
    assert v["routing"]["flipped"] == 0 and v["routing"]["short_max"] == 0
    if table == "ling":
        # logits just behind every chunk boundary and at every chunk's end
        assert v["probes"] == [0, 1, 2, 3, 63, 64, 65, 66, 67, 127,
                               128, 129, 130, 131, 149]
        # the rows' seats read back against the reference's states, a layer
        # each; the seats no row held are as they were made
        assert len(v["state"]["rms_rel_by_layer"]) == 6
        assert v["state"]["rms_rel_max"] < 1e-5, v["state"]
        assert v["state"]["stray_max"] == 0.0


def test_the_reference_follows_the_served_choices_and_counts_the_flips(
        engine, monkeypatch):
    """A served path that takes the 5th score where the 4th is due: the
    reference computes with the experts served (logits agree), counts every
    (token, layer) as flipped and says how far under the 4th the 5th lies."""
    def route_next(x, w_router, *, top_k, renormalise, scale):
        probs = jax.nn.softmax(jnp.dot(x, w_router), axis=-1)
        vals, idx = jax.lax.top_k(probs, top_k + 1)
        vals = jnp.concatenate([vals[:, :top_k - 1], vals[:, top_k:]], -1)
        idx = jnp.concatenate([idx[:, :top_k - 1], idx[:, top_k:]], -1)
        return idx, scale * vals / jnp.sum(vals, -1, keepdims=True)

    monkeypatch.setattr(moe, "route", route_next)
    v = _reference().compare(engine, SEED, T=72, chunk=32, n_decode=2)
    r = v["routing"]
    assert v["both"]["rms_rel"] < 1e-4, v
    assert r["flipped"] == r["token_layers"] == 2 * (72 + 2) * 4
    assert 0 < r["short_max"] < 1
    assert v["ok"] == (r["short_max"] <= v["short_tol"])


@pytest.mark.parametrize("table,variant,sees", [
    ("laguna", "no_scale", "logits"), ("laguna", "renorm_held", "logits"),
    ("ling", "no_groups", "routing"), ("ling", "no_bound", "logits"),
    ("longcat", "no_identity", "logits"), ("longcat", "no_scales", "logits"),
    ("longcat", "early_join", "logits")])
def test_the_reference_sees_a_part_of_the_mathematics_left_out(
        engines, table, variant, sees):
    """The controls the chip's limits rest on, at the tiny size: a reference
    that breaks a routing weight, leaves out the decay's bound, the identity
    experts' part or the latent scales, or joins a double layer's routed sum
    a row early, reads other logits altogether; one without the group limit
    reads the served choices as flips that lie well under its own 4th
    score."""
    t = TABLES[table]
    v = _reference(table).compare(engines(table), t["seed"], n_decode=2,
                                  variant=variant, **t["compare"])
    if sees == "routing":
        assert v["routing"]["flipped_share"] > 0.2, v["routing"]
        assert v["routing"]["short_max"] > 0.05
    else:
        assert not v["ok"] or v["both"]["rms_rel"] > 0.02, v
        assert v["both"]["rms_rel"] > 0.02


def test_a_window_ignored_in_decode_is_seen(engine, monkeypatch):
    real = M._paged_decode_attention
    monkeypatch.setattr(
        M, "_paged_decode_attention",
        lambda *a, window=0, **k: real(*a, window=0, **k))
    v = _reference().compare(engine, SEED, T=72, chunk=32, n_decode=2)
    assert v["prefill"]["rel"] < 1e-4 < v["decode"]["rel"], v


@pytest.mark.anyio
async def test_served_tokens_are_the_references_and_the_counters_add_up():
    """The same model through scheduler, chunked prefill and the autopilot
    decode window: greedy tokens equal the reference's on the served prefix,
    and every decode record carries the routing and window counters."""
    import asyncio

    cfg = _model()
    eng = InferenceEngine(cfg, _engine_config(prefill_chunk_tokens=16),
                          seed=SEED)
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(256, 512, size=n)]
               for n in (56, 49, 61)]

    async def one(i, p):
        out = []
        async for o in eng.submit(Request(
                request_id=f"r{i}", token_ids=p, max_tokens=6,
                ignore_eos=True)):
            out.append(o.token_id)
        return out

    try:
        got = await asyncio.gather(*(one(i, p) for i, p in enumerate(prompts)))
        records = list(eng.obs._records)
    finally:
        await eng.stop()
    ref = _reference()
    for p, toks in zip(prompts, got):
        full = np.asarray(p + toks[:-1], np.int32)
        hidden, _ = ref.reference_hidden(cfg, eng.params, full)   # own top-k
        logits = np.asarray(ref.head_logits(cfg, eng.params,
                                            hidden[len(p) - 1:]))
        assert toks == [int(t) for t in logits.argmax(-1)]
    decode = [r for r in records if r.kind == DECODE]
    assert decode
    n_sparse, k = 4, cfg.num_experts_per_token
    for r in decode:
        assert r.moe_pairs == r.live_rows * k * n_sparse      # no drop
        assert 0 < r.moe_pairs_held < r.moe_pairs             # routed over 16
        assert 0 < r.moe_experts_touched <= 8 * n_sparse
        assert 1 <= r.moe_load_max <= r.live_rows
        assert 0 < r.context_sum_window <= r.live_rows * 16
        assert r.context_sum_window < r.context_sum
        assert 0 < r.kv_blocks_walked_window <= r.kv_blocks_walked


# ------------------------- the expert layer ---------------------------------


def _experts(E=16, D=24, Fe=16, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((D, E)), jnp.float32),
            jnp.asarray(rng.standard_normal((E, D, Fe)) / np.sqrt(D),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((E, D, Fe)) / np.sqrt(D),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((E, Fe, D)) / np.sqrt(Fe),
                        jnp.float32))


def _routed_numpy(x, wr, wg, wu, wd, top_k, scale, held):
    """Per-token loop in float64: softmax over all experts, the top_k,
    weights over the chosen, only experts in ``held`` computed."""
    x, wr, wg, wu, wd = (np.asarray(a, np.float64)
                         for a in (x, wr, wg, wu, wd))
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        s = np.exp(x[n] @ wr - (x[n] @ wr).max())
        s /= s.sum()
        top = np.argsort(-s, kind="stable")[:top_k]
        for e in top:
            if e in held:
                g = x[n] @ wg[e]
                out[n] += (scale * s[e] / s[top].sum()
                           * ((g / (1 + np.exp(-g)) * (x[n] @ wu[e])) @ wd[e]))
    return out


def _routed(x, w, lo, hi, **kw):
    wr, wg, wu, wd = w
    return moe.routed_ffn(x, wr, wg[lo:hi], wu[lo:hi], wd[lo:hi], top_k=4,
                          held_start=lo, scale=2.5, interpret=True, **kw)


def test_two_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the routed parts of the two
    shares (experts 0-7 and 8-15) equal the uncut layer's routed part; the
    shared expert is counted once, outside."""
    w = _experts()
    x = jnp.asarray(np.random.default_rng(1).standard_normal((37, 24)),
                    jnp.float32)
    whole, s_all, c_all = _routed(x, w, 0, 16)
    first, s0, c0 = _routed(x, w, 0, 8)
    second, s1, c1 = _routed(x, w, 8, 16)
    # each share routes over all 16 and makes the same choices
    assert np.array_equal(np.asarray(c0), np.asarray(c_all))
    assert np.array_equal(np.asarray(c1), np.asarray(c_all))
    assert c_all.shape == (37, 4) and int(np.asarray(c_all).max()) > 7
    np.testing.assert_allclose(np.asarray(first + second), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(whole), _routed_numpy(x, *w, 4, 2.5, range(16)),
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(first), _routed_numpy(x, *w, 4, 2.5, range(8)),
        rtol=1e-4, atol=1e-4)
    s_all, s0, s1 = (np.asarray(s) for s in (s_all, s0, s1))
    assert s_all[0] == s0[0] == s1[0] == 37 * 4          # every pair kept
    assert s_all[1] == 37 * 4 and s0[1] + s1[1] == 37 * 4
    assert 0 < s0[1] < 37 * 4


def test_no_token_is_dropped_when_all_choose_the_same_expert():
    wr, wg, wu, wd = _experts()
    # expert 3 far ahead for every token, expert 12 (not held) second
    wr = jnp.zeros_like(wr).at[0, 3].set(40.0).at[0, 12].set(20.0)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 24)),
                    jnp.float32).at[:, 0].set(1.0)
    got, stats, chosen = moe.routed_ffn(
        x, wr, wg[:8], wu[:8], wd[:8], top_k=2, held_start=0, scale=2.5,
        interpret=True)
    assert np.all(np.asarray(chosen) == [3, 12])
    want = _routed_numpy(x, wr, wg, wu, wd, 2, 2.5, range(8))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert np.abs(want).min(axis=1).max() > 0 and np.all(
        np.abs(np.asarray(got)).sum(axis=1) > 0)       # every token served
    pairs, held, touched, zero, load = (int(v) for v in np.asarray(stats))
    assert (pairs, held, touched, zero, load) == (80, 40, 1, 0, 40)


def test_dead_rows_route_nowhere():
    w = _experts()
    x = jnp.asarray(np.random.default_rng(3).standard_normal((8, 24)),
                    jnp.float32)
    live = jnp.asarray([True, False, True, True, False, False, True, False])
    got, stats, _ = _routed(x, w, 0, 8, live=live)
    want = _routed_numpy(x, *w, 4, 2.5, range(8))
    idx = np.flatnonzero(np.asarray(live))
    np.testing.assert_allclose(np.asarray(got)[idx], want[idx],
                               rtol=1e-4, atol=1e-4)
    assert np.all(np.asarray(got)[~np.asarray(live)] == 0)
    assert int(stats[0]) == 4 * 4


def test_the_new_layer_has_no_capacity():
    import inspect

    src = inspect.getsource(moe.routed_ffn) + inspect.getsource(moe.route)
    assert "capacity" not in src.replace("no capacity", "")
    assert "one_hot" not in src


# ------------------------- the grouped matmul's call and tile ---------------

# (D, F) of the two tables at the benchmark's widths and the rows (pairs) of
# the benchmark's decode window and T=512 chunk: gate and up see (rows, D, F),
# down (rows, F, D): the eight shapes the tile was read at (PERF.md, PR 40)
WIDTHS = {"laguna": dict(D=3072, F=1024, decode=320, prefill=5120),
          "ling": dict(D=2560, F=768, decode=1024, prefill=4096)}
EIGHT_SHAPES = [(w[phase], k, n) for w in WIDTHS.values()
                for phase in ("decode", "prefill")
                for k, n in ((w["D"], w["F"]), (w["F"], w["D"]))]


@pytest.mark.parametrize("rows,k,n", EIGHT_SHAPES + [
    (148, 24, 16), (2048, 64, 32), (5120, 4096, 14336), (4096, 7168, 2048)])
def test_the_tile_follows_the_shape(rows, k, n):
    """tk is K or a divisor of it in whole lane tiles, and K wherever a
    block of K fits; tn divides N; the blocks fit the scoped VMEM; the
    row tile follows the rows (``routed_ffn`` pads the sorted pairs to it:
    ``test_health_names_the_tile_of_each_distinct_call``)."""
    tm, tk, tn = moe.gmm_tile(rows, k, n)
    assert k % tk == 0 and n % tn == 0
    assert tk == k or tk % 128 == 0
    assert tn == n or (tn % 128 == 0 and tn >= 512)
    assert moe.gmm_block_bytes(tm, tk, tn) < moe.GMM_VMEM_BYTES
    if moe.gmm_block_bytes(tm, k, min(n, 512)) < moe.GMM_VMEM_BYTES:
        assert tk == k                      # no k step, nothing read twice
    assert tm == (64 if rows <= 1024 else 128)


def _three_classes(D, F, held_start, seed=0):
    """8 routed experts of which the 4 from ``held_start`` are held, 2 a
    token; 330 tokens in three classes the router tells apart by one
    feature: 100 choose held 0 and one not held, 200 held 1 and held 3, 30
    held 3 and one not held; held 2 gets no token; every 7th row is dead.
    bfloat16, as served."""
    rng = np.random.default_rng(seed)
    h, o = held_start, (held_start + 4) % 8
    cls = np.repeat([0, 1, 2], [100, 200, 30])
    x = 0.5 * rng.standard_normal((330, D))
    x[:, :3] = np.eye(3)[cls]
    wr = np.zeros((D, 8))
    for c, (a, b) in enumerate(((h, o), (h + 1, h + 3), (h + 3, o + 1))):
        wr[c, a], wr[c, b] = 40.0, 20.0
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)          # noqa: E731
    w = [bf(rng.standard_normal(s) / np.sqrt(s[1]))
         for s in ((4, D, F), (4, D, F), (4, F, D))]
    live = np.arange(330) % 7 != 3
    return bf(x), jnp.asarray(wr, jnp.float32), w, live, cls


def _swiglu_by_expert(x, wr, w, live, held_start, scale):
    """Per token and per chosen held expert, written out in float64 on the
    bfloat16 values; the product rounded to bfloat16 as the layer does."""
    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa
    x, (wg, wu, wd) = f64(x), (f64(a) for a in w)
    logits = x @ np.asarray(wr, np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    top = np.argsort(-s, axis=-1, kind="stable")[:, :2]
    out = np.zeros_like(x)
    for e in range(4):
        rows = np.flatnonzero(live & (top == held_start + e).any(-1))
        g = x[rows] @ wg[e]
        hid = f64(jnp.asarray(g / (1 + np.exp(-g)) * (x[rows] @ wu[e]),
                              jnp.bfloat16))
        weight = scale * s[rows, held_start + e] / np.take_along_axis(
            s[rows], top[rows], axis=1).sum(-1)
        out[rows] += weight[:, None] * (hid @ wd[e])
    return out, top


@pytest.mark.parametrize("held_start", [0, 4])
@pytest.mark.parametrize("phase", ["decode", "prefill"])
@pytest.mark.parametrize("table", sorted(WIDTHS))
def test_routed_ffn_at_the_tiles_of_the_eight_shapes(table, phase,
                                                     held_start, monkeypatch):
    """The layer at the benchmark's widths and at the tile its call gets
    there (the rows cut down to 660 pairs), against the per-expert SwiGLU
    written out: a shard that starts at expert 0 and one that does not,
    dead rows, a group that spans three row tiles or four, an expert
    without a token, rows of no group behind the last one."""
    wd = WIDTHS[table]
    D, F = wd["D"], wd["F"]
    tile = moe.gmm_tile
    monkeypatch.setattr(moe, "gmm_tile", lambda rows, k, n, itemsize=2:
                        tile(wd[phase], k, n, itemsize))
    x, wr, w, live, cls = _three_classes(D, F, held_start)
    got, stats, chosen = moe.routed_ffn(
        x, wr, *w, top_k=2, held_start=held_start, scale=2.5,
        live=jnp.asarray(live), interpret=True)
    want, top = _swiglu_by_expert(x, wr, w, live, held_start, 2.5)
    assert np.array_equal(np.asarray(chosen), top)
    n = [int((live & (cls == c)).sum()) for c in range(3)]
    assert [int(v) for v in stats] == [
        2 * sum(n), n[0] + 2 * n[1] + n[2], 3, 0, n[1] + n[2]]
    tm = tile(wd[phase], D, F)[0]
    assert (n[0] + n[1] - 1) // tm - n[0] // tm >= 2     # 3 row tiles or 4
    got = np.asarray(got.astype(jnp.float32), np.float64)
    assert np.all(got[~live] == 0)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-2 * scale)
    assert np.abs(want[live]).max(axis=1).min() > 1e-3 * scale


def _poisoned_gmm(monkeypatch):
    """megablox ``gmm`` with every output row behind the last group set to
    NaN, which is what a row no group owns may hold on the chip."""
    import sys

    mod = sys.modules["jax.experimental.pallas.ops.tpu.megablox.gmm"]
    real = mod.gmm

    def gmm(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        behind = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(behind[:, None], jnp.nan, out)

    monkeypatch.setattr(mod, "gmm", gmm)


@pytest.mark.parametrize("held_start", [0, 8])
def test_rows_of_no_group_may_hold_anything(held_start, monkeypatch):
    """The grouped matmul is told the held groups alone and leaves the rows
    behind them as they were: a NaN there reaches no token."""
    w = _experts()
    x = jnp.asarray(np.random.default_rng(5).standard_normal((37, 24)),
                    jnp.float32)
    live = jnp.asarray(np.arange(37) % 5 != 2)
    want, s0, c0 = _routed(x, w, held_start, held_start + 8, live=live)
    _poisoned_gmm(monkeypatch)
    got, s1, c1 = _routed(x, w, held_start, held_start + 8, live=live)
    assert 0 < int(s1[1]) < int(s1[0])              # some rows are behind
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))


# sha256 of ``top_idx`` and the four ``MOE_STATS`` as the commit before PR 40
# (b5c78fa) gave them: the call of the grouped matmul changed, the choices
# and the counters did not.  PR 41 put a fifth counter between them
# (``moe_pairs_zero``), which reads 0 where the router has no such output
CHOICES_BEFORE = {
    ("softmax", 0, 8): "5d34d183ecc78a0d", ("softmax", 8, 16):
    "f3f81bc789ee5136", ("softmax", 6, 8): "6a88d42e8d91705b",
    ("sigmoid", 0, 8): "c23f1038c7c22954", ("sigmoid", 8, 16):
    "4e92fa99ae03aed1", ("sigmoid", 6, 8): "3f1f37c48f07fbba",
}


@pytest.mark.parametrize("router,lo,hi", sorted(CHOICES_BEFORE))
def test_choices_and_counters_are_what_they_were(router, lo, hi):
    rng = np.random.default_rng(40)
    f = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),  # noqa
                               jnp.float32)
    x, wr, wg, wu, wd = (f(40, 24), f(24, 16), f(16, 24, 16), f(16, 24, 16),
                         f(16, 16, 24))
    bias = jnp.asarray(0.05 * rng.normal(size=(16,)), jnp.float32)
    kw = {} if router == "softmax" else dict(
        score="sigmoid", bias=bias, n_group=8, topk_group=4)
    _, stats, chosen = moe.routed_ffn(
        x, wr, wg[lo:hi], wu[lo:hi], wd[lo:hi], top_k=4, held_start=lo,
        scale=2.5, live=jnp.asarray(np.arange(40) % 7 != 3), interpret=True,
        **kw)
    stats = np.asarray(stats)
    four = [moe.MOE_STATS.index(n) for n in (
        "moe_pairs", "moe_pairs_held", "moe_experts_touched", "moe_load_max")]
    assert stats[moe.MOE_STATS.index("moe_pairs_zero")] == 0
    assert hashlib.sha256(
        np.asarray(chosen).tobytes() + stats[four].tobytes()
    ).hexdigest()[:16] == CHOICES_BEFORE[router, lo, hi]


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, but a Pallas
    kernel's own body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("B,T", [(8, 1), (1, 512)])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_a_sparse_layer_is_three_grouped_matmuls_and_one_select(table, B, T):
    """A decode step and a T=512 chunk of each table, as traced: three
    grouped matmuls a sparse layer's body, and no select over a whole
    grouped-matmul output but ``routed_ffn``'s own over the down
    projection's (megablox zeroes the rows behind a SHARD's groups with one
    more; the call no longer poses as a shard)."""
    cfg = _model(table=table)
    eng = _engine_config(table, max_model_len=1024,
                         max_num_batched_tokens=512)
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, eng))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    seats = dict(seats=jnp.zeros((B,), jnp.int32)) if cfg.has_seat_state \
        else {}
    jaxpr = jax.make_jaxpr(
        lambda p, c, t, po, b: M.forward(cfg, eng, p, c, t, po, b, **seats)
    )(params, cache, i32(B, T), i32(B, T), i32(B, 64)).jaxpr
    pairs = B * T * cfg.num_experts_per_token
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    tm = moe.gmm_tile(pairs, D, F)[0]
    rows = -(-pairs // tm) * tm
    gmms = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"
            and tuple(e.outvars[0].aval.shape) in ((rows, F), (rows, D))
            and e.outvars[0].aval.dtype == jnp.float32]
    assert gmms and len(gmms) % 3 == 0
    assert sorted(e.outvars[0].aval.shape[1] for e in gmms) == sorted(
        [F, F, D] * (len(gmms) // 3))
    whole = [e for e in _eqns(jaxpr) if e.primitive.name == "select_n"
             and tuple(e.outvars[0].aval.shape) in ((rows, F), (rows, D))
             and e.outvars[0].aval.dtype == jnp.float32]
    # routed_ffn's own is over [pairs, D]: a whole output's where no row
    # was padded
    assert len(whole) == (len(gmms) // 3 if pairs == rows else 0)


@pytest.mark.parametrize("table", sorted(TABLES))
def test_health_names_the_tile_of_each_distinct_call(engines, table):
    """``/health`` (``device_report``) lists every distinct grouped-matmul
    call traced in the process with the tile it got, the one ``gmm_tile``
    gives for its shape: here at least a decode step's two."""
    eng = engines(table)
    cfg = eng.model_config
    B, k = 8, cfg.num_experts_per_token
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    i32 = lambda *s: jnp.zeros(s, jnp.int32)                 # noqa: E731
    seats = dict(seats=i32(B)) if cfg.has_seat_state else {}
    jax.eval_shape(lambda p, c: M.forward(
        cfg, eng.config, p, c, i32(B, 1), i32(B, 1), i32(B, 4), **seats),
        eng.params, eng.cache)
    # the record is the process's: this model's calls are those at its widths
    calls = [c for c in eng.device_report()["expert_tiles"]
             if (c["k"], c["n"]) in ((D, F), (F, D))]
    assert all(tuple(c["tile"]) == moe.gmm_tile(c["rows"], c["k"], c["n"])
               and c["rows"] % c["tile"][0] == 0 for c in calls)
    tm = moe.gmm_tile(B * k, D, F)[0]
    rows = -(-B * k // tm) * tm
    assert {(rows, D, F), (rows, F, D)} <= {
        (c["rows"], c["k"], c["n"]) for c in calls}


# ------------------------- the window in the kernels ------------------------


def _paged(B, W, KV, bs, hd, seed=0):
    rng = np.random.default_rng(seed)
    nb = 1 + B * W
    k = rng.standard_normal((nb, KV, bs, hd), dtype=np.float32)
    v = rng.standard_normal((nb, KV, bs, hd), dtype=np.float32)
    tables = np.zeros((B, W), np.int32)
    for b in range(B):
        tables[b] = 1 + b * W + np.arange(W)
    return k, v, tables


def _gathered(k, tables, bs):
    B, W = tables.shape
    return np.asarray(k)[tables].transpose(0, 1, 3, 2, 4).reshape(
        B, W * bs, k.shape[1], k.shape[3])


@pytest.mark.parametrize("G", [2, 3])
@pytest.mark.parametrize("kv_tile", [16, 32])
def test_decode_walk_with_a_lower_bound_matches_the_masked_einsum(kv_tile, G):
    """Contexts below, at and above the window (16), across a tile edge and
    many windows long; groups of 2 and 3 query heads a KV head."""
    bs, W, KV, hd, window = 16, 8, 2, 16, 16
    ctxs = np.asarray([5, 16, 17, 31, 32, 33, 48, 49, 97, 128, 0, 1],
                      np.int32)
    B, H = len(ctxs), KV * G
    k, v, tables = _paged(B, W, KV, bs, hd)
    q = np.random.default_rng(9).standard_normal((B, H, hd),
                                                 dtype=np.float32)
    got = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables),
        jnp.asarray(ctxs), block_size=bs, kv_tile=kv_tile, interpret=True,
        window=window)
    want = M._attention(
        jnp.asarray(q)[:, None], jnp.asarray(_gathered(k, tables, bs)),
        jnp.asarray(_gathered(v, tables, bs)),
        jnp.asarray(ctxs - 1)[:, None], window)[:, 0]
    live = ctxs > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[~live] == 0)
    # and it is the window that is compared: the unmasked result differs
    full = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables),
        jnp.asarray(ctxs), block_size=bs, kv_tile=kv_tile, interpret=True)
    long = ctxs > window
    assert np.abs(np.asarray(full) - np.asarray(got))[long].max() > 1e-3
    np.testing.assert_allclose(np.asarray(full)[~long],
                               np.asarray(got)[~long], rtol=2e-5, atol=2e-5)


def test_ragged_walk_with_a_lower_bound_matches_the_masked_einsum():
    """A chunk of 8 queries a row whose tile's first query sets the bound."""
    bs, W, KV, G, hd, window, T = 16, 8, 2, 3, 16, 16, 8
    ctxs = np.asarray([8, 20, 40, 41, 100, 128], np.int32)
    B, H = len(ctxs), KV * G
    k, v, tables = _paged(B, W, KV, bs, hd, seed=4)
    q = np.random.default_rng(8).standard_normal((B, T, H, hd),
                                                 dtype=np.float32)
    pos = (ctxs[:, None] - T + np.arange(T)[None, :]).astype(np.int32)
    got = paged_attention_ragged(
        jnp.asarray(q.reshape(B * T, H, hd)), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(tables), jnp.arange(B + 1, dtype=jnp.int32) * T,
        jnp.full((B,), T, jnp.int32), jnp.asarray(ctxs), block_size=bs,
        max_q_len=T, kv_tile=16, interpret=True, window=window)
    want = M._attention(
        jnp.asarray(q), jnp.asarray(_gathered(k, tables, bs)),
        jnp.asarray(_gathered(v, tables, bs)), jnp.asarray(pos), window)
    np.testing.assert_allclose(np.asarray(got).reshape(B, T, H, hd),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_tile,window,contexts,want", [
    (256, 512, [100], 1 * 16),            # below the window: from page 0
    (256, 512, [512], 2 * 16),
    (256, 512, [513], 3 * 16),            # key 1 is in tile 0 still
    (256, 512, [2050], (9 - 6) * 16),     # tiles 6, 7, 8 of 9
    (256, 0, [2050], 9 * 16),
    (16, 16, [5, 16, 17, 33, 0], 1 + 1 + 2 + 2 + 0),
])
def test_kv_blocks_walked_starts_at_the_windows_tile(kv_tile, window,
                                                     contexts, want):
    assert kv_blocks_walked(contexts, kv_tile=kv_tile, block_size=16,
                            window=window) == want


# ------------------------- rope ---------------------------------------------


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
@pytest.mark.parametrize("rehearse", [True, False])
def test_rope_of_a_kind_is_the_references(kind, rehearse):
    """YaRN on half of each head and plain rope on all of it, against the
    reference's own tables, at the tiny and the published parameters."""
    cfg = _model(rehearse)
    rope = cfg.rope_of(next(k for k in cfg.attn_kinds if k.name == kind))
    hd = cfg.head_dim_
    pos = np.asarray([[0, 1, 7, 500, 4095, 70000]], np.int32)
    x = np.random.default_rng(3).standard_normal(
        (1, pos.shape[1], 3, hd)).astype(np.float32)
    got = np.asarray(M._rope_kind(jnp.asarray(x), jnp.asarray(pos), rope))
    cos, sin, rot = _reference().rope_tables(rope, hd, pos[0])
    cos, sin = np.asarray(cos)[:, None], np.asarray(sin)[:, None]
    a, b, rest = x[0, ..., :rot // 2], x[0, ..., rot // 2:rot], x[0, ..., rot:]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)
    assert rot == (hd // 2 if kind == "full_attention" else hd)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    if kind == "full_attention":
        assert np.array_equal(got[..., rot:], x[..., rot:])   # passes through
        inv, scale = M.rope_frequencies(rope, hd)
        plain = 1.0 / float(rope["rope_theta"]) ** (
            np.arange(rot // 2) / (rot // 2))
        # the fastest dims keep their frequency, the slowest are divided
        assert inv[0] == pytest.approx(plain[0])
        assert inv[-1] == pytest.approx(plain[-1] / rope["factor"], rel=1e-5)
        assert scale == pytest.approx(
            rope.get("attention_factor", 0.1 * np.log(rope["factor"]) + 1))


def test_plain_rope_of_a_kind_is_the_one_kind_models():
    # (the kind's frequencies are made in float64 at trace time, the
    # one-kind model's in float32 on the device: equal to rounding)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, 4, 16)),
                    jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 900], [5, 6, -1, -1, -1]], jnp.int32)
    a = M._rope(x, pos, 10000.0)
    b = M._rope_kind(x, pos, {"rope_type": "default", "rope_theta": 10000})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


# ------------------------- a model without a table --------------------------

# sha256 (16 hex) of the parameters' bytes at PRNGKey(7) and of the lowered
# StableHLO of one ``forward`` (2 rows of 6 tokens, 2 pages a row). The
# parameters' were taken on the commit before the table (a2f7c1f), where the
# program's were 977b58bc4b45bc16 / 5ea2ed3b39a0df13 and the table left them
# so; the program's were taken again when PR 33 changed how every step
# writes K and V (``model._kv_write``): a change that means to leave the
# one-kind model's program alone still finds it pinned here.
BEFORE = {
    "tiny": ("2df05571f5908433", "d06bc3fd4c0a4a30"),
    "tiny_moe": ("2370a9c04f43bbd6", "135af208191e35d2"),
}


@pytest.mark.parametrize("preset", sorted(BEFORE))
def test_a_model_without_a_table_is_what_it_was(preset):
    cfg = getattr(ModelConfig, preset)()
    assert not cfg.has_table and len(cfg.attn_kinds) == 1
    eng = EngineConfig(num_blocks=16, attention_impl="einsum")
    params = M.init_params(jax.random.PRNGKey(7), cfg)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    cache = M.init_cache(cfg, eng)
    tok = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) + 300
    pos = jnp.tile(jnp.arange(6, dtype=jnp.int32), (2, 1))
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    f = jax.jit(lambda p, c, t, po, b: M.forward(cfg, eng, p, c, t, po, b))
    text = f.lower(params, cache, tok, pos, bt).as_text()
    assert (h.hexdigest()[:16],
            hashlib.sha256(text.encode()).hexdigest()[:16]) == BEFORE[preset]


# ------------------------- what refuses a table ------------------------------


@pytest.mark.parametrize("table", sorted(TABLES))
def test_what_cannot_run_a_table_says_so_when_it_is_built(cpu_devices, table):
    from dynamo_tpu.parallel import layout, pp_serving

    cfg = _model(table=table)
    mesh = layout.make_mesh((1, 2), devices=cpu_devices[:2])
    with pytest.raises(ValueError, match="--mesh 1,1"):
        M.init_params_sharded(jax.random.PRNGKey(0), cfg, mesh)
    with pytest.raises(ValueError, match="weight-dtype int8"):
        M.init_params_sharded(jax.random.PRNGKey(0), cfg, None, "int8")
    with pytest.raises(ValueError, match="pipeline-parallel"):
        pp_serving.raw_pp_step_fn(cfg, _engine_config(table), mesh)
    with pytest.raises(ValueError, match="--mesh 1,1"):
        InferenceEngine(cfg, _engine_config(table, mesh_shape=(1, 2)),
                        seed=0)


def test_the_encoder_runs_a_table(engine):
    """``encode_forward`` calls the same layer body: its pooled states are
    those of the reference's hidden states."""
    cfg = engine.model_config
    toks = np.random.default_rng(2).integers(256, 512, size=(1, 40))
    pos = np.arange(40, dtype=np.int32)[None]
    got = M.encode_forward(cfg, engine.params, jnp.asarray(toks, jnp.int32),
                           jnp.asarray(pos))
    hidden, _ = _reference().reference_hidden(cfg, engine.params, toks[0])
    want = np.asarray(hidden).mean(0)
    want /= np.linalg.norm(want)
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-4, atol=1e-4)


# ------------------------- FLOPs ---------------------------------------------


def test_flops_count_a_tables_layers_by_kind():
    cfg = _model(rehearse=False)
    D, hd, KV = 3072, 128, 8
    attn = lambda H: 2 * D * H * hd + 2 * D * KV * hd + D * H   # noqa: E731
    small = 2 * attn(48) + 3 * attn(72) + 5 * 2 * D + D
    dense = 3 * D * 12288
    sparse = D * 256 + 3 * D * 1024                 # router + shared expert
    expert = 3 * D * 1024
    embed = 50176 * D
    assert F.param_count(cfg) == (small + dense + 4 * sparse
                                  + 4 * 128 * expert + 2 * embed)
    assert F.param_count(cfg) == 5572076544          # ISSUE.md's 5.572 B
    # a token multiplies by 10 experts, half of them held here on average
    assert F.active_param_count(cfg) == (small + dense + 4 * sparse
                                         + 4 * 5 * expert + embed)
    fm = F.FlopsModel(cfg)
    assert fm.attn_coef == 4.0 * hd * (2 * 48 + 3 * 72)
    assert fm.step_flops(1, 0) == 2.0 * F.active_param_count(cfg)
    tiny = ModelConfig.tiny()
    assert F.FlopsModel(tiny).attn_coef == (
        4.0 * tiny.num_layers * tiny.num_heads * tiny.head_dim_)
