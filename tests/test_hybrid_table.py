"""A table whose layers keep no K and V (PR 34): linear-attention (KDA)
layers with a float32 state a sequence in the scheduler's seat, a latent
(MLA) layer on a paged latent cache, and a sigmoid router limited to 4 of 8
groups — the tiny configuration of
``benchmarks/chip/configs/ling-3.0-flash-ep8.json`` (``rehearse.model``: 7
layers in the published period of six, 4 heads of 16, 16 routed experts in 8
groups of which 2 are held) against ``references/ling.py`` and against plain
numpy.  CPU, float32; Pallas kernels interpreted.  What holds for any
table (a table that contradicts itself, the system against its reference, a
reference that leaves a part out, what refuses a table) runs for this one
too in ``test_layer_table.py``."""

import asyncio
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.observability import compilewatch
from dynamo_tpu.observability import flops as F
from dynamo_tpu.observability.stepstats import DECODE, PREFILL
from dynamo_tpu.ops import delta_rule as DR
from dynamo_tpu.parallel import moe

from test_layer_table import TABLES, _engine_config as _table_engine_config
from test_layer_table import _model as _table_model
from test_layer_table import _reference as _table_reference

SEED = TABLES["ling"]["seed"]


def _model(rehearse: bool = True, **replace) -> ModelConfig:
    return _table_model(rehearse, "ling", **replace)


def _reference():
    return _table_reference("ling")


def _engine_config(**kw) -> EngineConfig:
    return _table_engine_config("ling", **kw)


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(_model(), _engine_config(), seed=SEED)


# ------------------------- the configuration --------------------------------


def test_the_table_reads_the_published_keys():
    cfg = _model(rehearse=False)
    assert cfg.layer_types == ("linear_attention",) * 5 + (
        "mla_attention", "linear_attention")
    assert cfg.cache_kinds == ("latent", "state")
    assert cfg.has_seat_state and cfg.has_latent_cache
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.short_conv_kernel_size, cfg.kda_lower_bound) == (4, -5)
    assert (cfg.score_function, cfg.n_group, cfg.topk_group,
            cfg.moe_router_enable_expert_bias) == ("sigmoid", 8, 4, True)
    assert cfg.experts_held == (0, 64) and cfg.num_routed_experts == 512
    assert M.latent_width(cfg) == 640          # 576 in whole lane tiles
    # the issue's count of what this chip holds: 2.80 B parameters
    assert F.param_count(cfg) == 2803845056


def test_parameters_and_cache_follow_the_kinds(engine):
    cfg, eng = engine.model_config, engine.config
    layers = engine.params["layers"]
    assert "wk" not in layers and "wv" not in layers    # nobody keeps K / V
    assert layers["wq"]["linear_attention"].shape == (6, 64, 64)
    assert layers["wq"]["mla_attention"].shape == (1, 64, 4 * 24)
    assert layers["kda_conv"].shape == (6, 4, 3 * 64)
    assert layers["mla_wukv"].shape == (1, 32, 4 * 32)
    assert layers["router_bias"].shape == (6, 16)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(engine.params))
    assert n == F.param_count(cfg)
    cache = engine.cache
    assert sorted(cache) == ["conv", "latent", "state"]
    assert [a.shape for a in cache["latent"]] == [(96, 1, 16, 128)]
    assert len(cache["state"]) == len(cache["conv"]) == 6
    S = eng.max_num_seqs
    assert cache["state"][0].shape == (S + 1, 4, 16, 16)
    assert cache["state"][0].dtype == jnp.float32
    assert cache["conv"][0].shape == (S + 1, 3, 3 * 64)


def test_the_ladder_of_decode_buckets_follows_the_seats():
    assert EngineConfig().decode_buckets == (8, 16, 32, 64)
    assert EngineConfig(max_num_seqs=32).decode_buckets == (8, 16, 32, 64)
    assert EngineConfig(max_num_seqs=128).decode_buckets == (
        8, 16, 32, 64, 128)
    with pytest.raises(ValueError, match="largest decode bucket"):
        EngineConfig(max_num_seqs=128, decode_buckets=(8, 64))


# ------------------------- (1) the system against the reference -------------


def test_a_state_kept_in_bfloat16_is_seen_in_the_state_and_the_logits():
    """The control the chip's limit on ``state.rms_rel_first`` rests on, at
    the tiny size: the same program with its pool in bfloat16 (the decode
    step then runs the XLA recurrence, which a float32 pool leaves to the
    kernel) reads 1000 times further from the reference's state."""
    eng = InferenceEngine(_model(state_dtype="bfloat16"), _engine_config(),
                          seed=SEED)
    assert {a.dtype for a in eng.cache["state"]} == {jnp.dtype("bfloat16")}
    v = _reference().compare(eng, SEED, T=150, chunk=64, n_decode=24)
    assert v["state"]["rms_rel_first"] > 1e-3, v["state"]
    assert v["both"]["rms_rel"] > 1e-3, v["both"]
    assert v["state"]["stray_max"] == 0.0


def test_a_state_lost_at_a_chunk_boundary_is_seen(engine, monkeypatch):
    """What the probes behind a boundary are for: with the seats' states
    zeroed between the served chunks, the latent cache and every weight
    still right, the logits just behind the boundary and the states read
    back are far from the reference's."""
    ref = _reference()
    real = ref.served_step

    def forgetful(cfg, eng, mesh, at):
        step = real(cfg, eng, mesh, at)

        def run(params, cache, tok, p, tb, seats):
            cache, logits, experts = step(params, cache, tok, p, tb, seats)
            if tok.shape[1] > 1:
                cache = dict(cache, state=[jnp.zeros_like(a)
                                           for a in cache["state"]])
            return cache, logits, experts

        return run

    monkeypatch.setattr(ref, "served_step", forgetful)
    v = ref.compare(engine, SEED, T=150, chunk=64, n_decode=6)
    assert not v["ok"]
    assert v["prefill"]["rel"] > 0.06 and v["state"]["rms_rel_first"] > 0.1, v


# ------------------------- (2) the chunked form -----------------------------


def _kda_inputs(B, T, H, d, seed=0, scale=3.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = DR.l2norm(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = DR.l2norm(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = -5.0 * jax.nn.sigmoid(scale * jax.random.normal(ks[3], (B, T, H, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, d, d))
    return S0, q, k, v, g, beta


@pytest.mark.parametrize("T,mid,pads", [
    (64, False, 0), (150, True, 0), (37, True, 0), (128, True, 41),
    (16, False, 5),
])
def test_the_chunked_form_is_the_token_recurrence(T, mid, pads):
    """float32: whole and ragged chunks, a chunk that starts mid-sequence
    (from a state that is not zero), pads inside a chunk (decay 1, step
    size 0: the state a pad tail leaves is the state before it)."""
    S0, q, k, v, g, beta = _kda_inputs(2, T, 3, 16, seed=T)
    if not mid:
        S0 = jnp.zeros_like(S0)
    if pads:
        live = (jnp.arange(T) < T - pads)[None, :, None]
        g = jnp.where(live[..., None], g, 0.0)
        beta = jnp.where(live, beta, 0.0)
    with jax.default_matmul_precision("highest"):
        o1, S1 = DR.kda_scan(S0, q, k, v, g, beta)
        o2, S2 = jax.jit(DR.kda_chunked)(S0, q, k, v, g, beta)
    n = T - pads
    np.testing.assert_allclose(o2[:, :n], o1[:, :n], atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(S2, S1, atol=2e-5, rtol=1e-4)
    if pads:        # the pad tail changed nothing
        _, S_live = DR.kda_scan(S0, q[:, :n], k[:, :n], v[:, :n], g[:, :n],
                                beta[:, :n])
        np.testing.assert_allclose(S2, S_live, atol=2e-5, rtol=1e-4)


def test_the_chunked_form_holds_at_the_decays_bound_and_with_equal_keys():
    """What the bound on the decay is for: at -5 a token the split of
    ``exp(G_t - G_r)`` around a block's reference stays inside float32; and
    keys that repeat (the triangular system at its worst) are solved by
    substitution, not by powers that cancel."""
    S0, q, k, v, g, beta = _kda_inputs(1, 128, 2, 16, seed=5)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    ones = 0.999 * jnp.ones_like(beta)
    for gg, tol in ((jnp.zeros_like(g), 2e-5), (jnp.full_like(g, -5.0), 5e-3)):
        o1, S1 = DR.kda_scan(S0, q, k, v, gg, ones)
        o2, S2 = jax.jit(DR.kda_chunked)(S0, q, k, v, gg, ones)
        assert bool(jnp.isfinite(o2).all())
        np.testing.assert_allclose(o2, o1, atol=tol)
        np.testing.assert_allclose(S2, S1, atol=tol)


def test_the_short_convolution_carries_its_last_inputs():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 10, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    zero = jnp.zeros((2, 3, 6))
    whole, _ = DR.short_conv(u, zero, w, jnp.array([10, 10]))
    want = sum(w[j] * jnp.pad(u, ((0, 0), (3, 0), (0, 0)))[:, j:j + 10]
               for j in range(4))
    np.testing.assert_allclose(whole, want, atol=1e-6)
    # 6 tokens, then 4 of a chunk padded to 8; row 1 is dead in the second
    a, st = DR.short_conv(u[:, :6], zero, w, jnp.array([6, 6]))
    tail = jnp.pad(u[:, 6:], ((0, 0), (0, 4), (0, 0)))
    b, st2 = DR.short_conv(tail, st, w, jnp.array([4, 0]))
    np.testing.assert_allclose(a, whole[:, :6], atol=1e-6)
    np.testing.assert_allclose(b[0, :4], whole[0, 6:], atol=1e-6)
    np.testing.assert_array_equal(st2[0], u[0, 7:10])
    np.testing.assert_array_equal(st2[1], st[1])        # dead: as it was


# ------------------------- (3) the latent layer -----------------------------


def test_absorbed_and_expanded_latent_attention_agree(engine):
    cfg = engine.model_config
    p, _, kind = M.layer_params(cfg, engine.params["layers"], 5)
    assert kind.name == "mla_attention"
    rng = np.random.default_rng(1)
    B, S = 2, 48
    h = jnp.asarray(rng.normal(size=(B, S, cfg.hidden_size)), jnp.float32)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    _, q_nope, q_pe, latent = M.latent_inputs(cfg, kind, p, h, pos)
    assert latent.shape == (B, S, 1, 128)
    np.testing.assert_array_equal(latent[..., 40:], 0)   # 32 + 8, then zeros
    ctx = latent[:, :, 0]
    full = M.latent_attention(cfg, p, q_nope, q_pe, ctx, pos, absorbed=False)
    same = M.latent_attention(cfg, p, q_nope, q_pe, ctx, pos, absorbed=True)
    np.testing.assert_allclose(same, full, atol=2e-5, rtol=1e-4)
    # the decode step through the kernel: the last query over paged latents
    bs = 16
    plane = jnp.zeros((1 + B * 3, 1, bs, 128)).at[1:].set(
        ctx.reshape(B * 3, bs, 128)[:, None])
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    got = M._paged_latent_decode(
        cfg, engine.config, None, p, q_nope[:, -1:], q_pe[:, -1:], plane,
        tables, jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(got[:, 0], full[:, -1], atol=2e-5, rtol=1e-4)


def test_the_rope_of_a_latent_layer_turns_interleaved_pairs():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 3, 2, 8)),
                    jnp.float32)
    pos = jnp.asarray([[0, 5, 9]], jnp.int32)
    rope = {"rope_theta": 6e6, "rope_type": "default", "interleave": True}
    got = np.asarray(M._rope_kind(x, pos, rope))
    inv = 6e6 ** (-np.arange(0, 8, 2) / 8)
    ang = np.asarray(pos, np.float64)[..., None] * inv            # [1, 3, 4]
    c, s = np.cos(ang)[:, :, None], np.sin(ang)[:, :, None]
    xe, xo = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    want = np.stack([xe * c - xo * s, xo * c + xe * s], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[:, 0], np.asarray(x)[:, 0])  # position 0


# ------------------------- (4) the router and the shares --------------------


def _experts(E=16, D=24, Fe=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),  # noqa
                               jnp.float32)
    return (f(40, D), f(D, E), f(E, D, Fe), f(E, D, Fe), f(E, Fe, D),
            jnp.asarray(0.05 * rng.normal(size=(E,)), jnp.float32))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed outputs of the eight ``expert_shard``s (each a group of 2
    of 16 experts) equal the reference's layer over all 16; the shared
    expert is every chip's alike and counted once (it is outside
    ``routed_ffn``).  A token's chosen experts lie in 4 of the 8 groups at
    most, and no group it was not given."""
    ref = _reference()
    x, wr, wg, wu, wd, bias = _experts()
    kw = dict(top_k=4, scale=2.5, score="sigmoid", bias=bias, n_group=8,
              topk_group=4, interpret=True)
    total, chosen = 0.0, None
    for shard in range(8):
        lo = 2 * shard
        out, stats, chosen = moe.routed_ffn(
            x, wr, wg[lo:lo + 2], wu[lo:lo + 2], wd[lo:lo + 2],
            held_start=lo, **kw)
        total = total + out
    groups = np.asarray(chosen) // 2
    assert max(len(set(row)) for row in groups) <= 4
    with jax.default_matmul_precision("highest"):
        weight, _, flipped, short = ref.router_weights(
            x, wr, bias, top_k=4, n_group=8, topk_group=4, scale=2.5,
            forced=chosen)
        want = sum(weight[:, e:e + 1] * ref.swiglu(x, wg[e], wu[e], wd[e])
                   for e in range(16))
    assert not bool(flipped.any()) and float(short.max()) == 0
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)
    # the weights are the unbiased scores over their sum, times the scale
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-5)


def test_the_bias_moves_the_choice_and_not_the_weights():
    x, wr, *_ = _experts(seed=3)
    big = jnp.zeros((16,)).at[7].set(10.0)
    idx, w = moe.route(x, wr, top_k=4, renormalise=True, scale=1.0,
                       score="sigmoid", bias=big, n_group=8, topk_group=4)
    assert bool((np.asarray(idx) == 7).any(axis=1).all())   # always chosen
    s = jax.nn.sigmoid(jnp.dot(x, wr, precision="highest"))
    own = jnp.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(w, own / own.sum(-1, keepdims=True), rtol=1e-5)


def _softmax_top_k(x, w_router, *, top_k, renormalise, scale, **_):
    """The router as it was before it learned scores, bias and groups."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    vals, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if renormalise:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return idx, vals * scale


def test_the_softmax_router_gives_what_it_gave():
    """One router path: with the keywords at their defaults (Laguna) the
    choice and the weights are those of the softmax top-k that was there,
    to the last bit."""
    x, wr, *_ = _experts(seed=4)
    kw = dict(top_k=4, renormalise=True, scale=2.5)
    want = _softmax_top_k(x, wr, **kw)
    for got in (moe.route(x, wr, **kw),
                jax.jit(lambda a, b: moe.route(a, b, **kw))(x, wr)):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])


# ------------------------- (5) state hygiene --------------------------------


def _run(eng, prompts, max_tokens, gap=0.0):
    async def one(i, p, n):
        if gap:
            await asyncio.sleep(gap * i)
        out = []
        async for o in eng.submit(Request(
                request_id=f"r{i}", token_ids=p, max_tokens=n,
                ignore_eos=True)):
            out.append(o.token_id)
        return out

    async def go():
        try:
            return await asyncio.gather(*(
                one(i, p, n) for i, (p, n) in
                enumerate(zip(prompts, max_tokens))))
        finally:
            await eng.stop()

    return asyncio.run(go())


def _fresh(prompt, n):
    eng = InferenceEngine(_model(), _engine_config(), seed=SEED)
    return _run(eng, [prompt], [n])[0]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(256, 512, size=n)] for n in lengths]


def test_the_engine_serves_the_reference_and_counts_its_seats(monkeypatch):
    """Store-less engine loop: packed prefill chunks by seat, then the
    decode window; rows finish at different steps, so windows run with dead
    rows beside live ones.  Greedy tokens are the reference's own; the
    records carry the two counters; the trash seat and block 0 are zeros."""
    # the recorder keeps a window of records: wide enough for a slow host
    monkeypatch.setenv("DYNTPU_OBS_WINDOW_S", "3600")
    cfg = _model()
    buckets = (16, 32, 64)
    eng = InferenceEngine(cfg, _engine_config(prefill_buckets=buckets),
                          seed=SEED)
    # every (T, W) program, the decode bucket and the control deltas were
    # compiled when the engine was built, on pads that changed nothing
    assert sorted(eng._packed_prefill_fns) == [
        (T, W) for T in buckets for W in (4, 16)]
    for layer in sum(eng.cache.values(), []):
        assert not np.asarray(layer).any()
    built = compilewatch.snapshot()["compiles_by_fn"]
    prompts = _prompts((70, 49, 61))
    got = _run(eng, prompts, [9, 4, 7])
    served = compilewatch.snapshot()["compiles_by_fn"]
    for fn in ("packed_prefill", "decode_window", "ctl_delta"):
        assert served.get(fn) == built.get(fn), (fn, built, served)
    records = list(eng.obs._records)
    ref = _reference()
    for p, toks in zip(prompts, got):
        full = np.asarray(p + toks[:-1], np.int32)
        hidden, _, _ = ref.reference_hidden(cfg, eng.params, full)
        logits = np.asarray(ref.head_logits(cfg, eng.params,
                                            hidden[len(p) - 1:]))
        assert toks == [int(t) for t in logits.argmax(-1)]
    decode = [r for r in records if r.kind == DECODE]
    prefill = [r for r in records if r.kind == PREFILL]
    assert decode and prefill
    assert all(r.state_rows == r.live_rows for r in decode)
    assert all(r.latent_context_sum == r.context_sum > 0 for r in decode)
    assert all(r.state_rows == 1 for r in prefill)
    # a chunk runs its own bucket's program, a tail of 6 tokens the 16's
    assert sorted((r.real_tokens, r.bucket) for r in prefill) == [
        (6, 16), (49, 64), (61, 64), (64, 64)]
    # the CPU's einsum attends all the table gathers: 4 or 16 blocks
    assert sorted({(r.latent_keys_walked, r.latent_keys_gathered)
                   for r in prefill}) == [(64, 64), (256, 256)]
    assert all(r.latent_keys_walked == 0 for r in decode)
    assert all(r.moe_pairs == r.live_rows * 4 * 6 for r in decode)
    S = eng.config.max_num_seqs
    for key in ("state", "conv"):
        for layer in eng.cache[key]:
            np.testing.assert_array_equal(layer[S], 0)
    np.testing.assert_array_equal(eng.cache["latent"][0][0], 0)
    assert any(float(jnp.abs(layer[:S]).max()) > 0
               for layer in eng.cache["state"])


def test_a_seat_reused_by_a_new_request_starts_from_zeros():
    """One seat (``max_num_seqs`` 1): the second request takes the seat the
    first left its state in, and reads as a fresh engine does."""
    prompts = _prompts((40, 33), seed=1)
    eng = InferenceEngine(_model(), _engine_config(max_num_seqs=1),
                          seed=SEED)
    got = _run(eng, prompts, [5, 6])
    assert got[0] == _fresh(prompts[0], 5)
    assert got[1] == _fresh(prompts[1], 6)


def test_a_preempted_request_that_starts_over_reads_as_fresh(monkeypatch):
    """A pool too small for both sequences to grow: one is preempted by
    recompute, prefilled again from its first token (its seat's state reset
    at position 0) and continues where it was."""
    calls = []
    real = Scheduler.preempt_recompute

    def spy(self, seq):
        calls.append(seq.seq_id)
        return real(self, seq)

    monkeypatch.setattr(Scheduler, "preempt_recompute", spy)
    prompts = _prompts((40, 40), seed=2)
    eng = InferenceEngine(_model(), _engine_config(num_blocks=9,
                                                   watermark=0.0),
                          seed=SEED)
    got = _run(eng, prompts, [30, 30], gap=0.05)
    assert calls, "the pool never ran dry: no preemption was tested"
    for p, toks in zip(prompts, got):
        assert toks == _fresh(p, 30)


# ------------------------- (6) what the state forbids -----------------------


def test_no_prefix_hit_where_a_sequence_keeps_a_state():
    """The same prompt twice: the blocks are sealed and cached, and the
    second request still computes every token (nobody holds the state the
    skipped tokens would have left)."""
    prompt = _prompts((48,), seed=3)[0]
    eng = InferenceEngine(_model(), _engine_config(), seed=SEED)
    assert eng.scheduler.seat_state
    chunks, dispatch = [], eng._dispatch_prefill

    def spy(chunk, obs_out=None):
        chunks.append((chunk.start, chunk.length))
        return dispatch(chunk, obs_out)

    eng._dispatch_prefill = spy

    async def go():
        outs = []
        try:
            for i in range(2):
                toks = []
                async for o in eng.submit(Request(
                        request_id=f"p{i}", token_ids=prompt, max_tokens=3,
                        ignore_eos=True)):
                    toks.append(o.token_id)
                outs.append(toks)
        finally:
            await eng.stop()
        return outs

    a, b = asyncio.run(go())
    assert a == b
    assert eng.scheduler.stats.prefix_cache_hits == 0
    assert chunks == [(0, 48), (0, 48)]      # both from their first token
    # a model of K and V pages on the same scheduler does hit
    sched = Scheduler(_engine_config())
    assert not sched.seat_state


def test_what_moves_pages_refuses_the_table_when_it_is_built():
    """Beside what refuses any table (``test_layer_table.py``)."""
    from dynamo_tpu.disagg.handlers import DecodeHandler, PrefillHandler

    cfg = _model()
    with pytest.raises(ValueError, match="speculative decoding"):
        InferenceEngine(cfg, _engine_config(spec_mode="ngram"), seed=0)
    with pytest.raises(ValueError, match="kv-dtype int8"):
        InferenceEngine(cfg, _engine_config(kv_dtype="int8"), seed=0)
    with pytest.raises(ValueError, match="KV block transfer"):
        M.cache_payload_keys(cfg, _engine_config())
    with pytest.raises(ValueError, match="multimodal"):
        M.make_mm_prefill_fn(cfg, _engine_config(), None)
    with pytest.raises(ValueError, match="encoder"):
        M.make_encode_fn(cfg)
    eng = InferenceEngine(cfg, _engine_config(), seed=0)
    assert eng._kv_extract is None and eng._kv_inject is None
    with pytest.raises(ValueError, match="KVBM"):
        eng.attach_kvbm()
    with pytest.raises(ValueError, match="disaggregated prefill"):
        PrefillHandler(eng)
    with pytest.raises(ValueError, match="disaggregated decode"):
        DecodeHandler(eng)
    # the message names what it refuses
    with pytest.raises(ValueError, match="linear_attention.*mla_attention"):
        M.refuse_unpaged(cfg, "x")
    with pytest.raises(ValueError, match="the rows' seats"):
        M.forward(cfg, eng.config, eng.params, eng.cache,
                  jnp.zeros((1, 16), jnp.int32),
                  jnp.zeros((1, 16), jnp.int32), jnp.zeros((1, 4), jnp.int32))


def test_a_model_of_k_and_v_pages_is_refused_nothing():
    tiny = ModelConfig.tiny()
    assert tiny.cache_kinds == ("kv",)
    M.refuse_unpaged(tiny, "anything")
    assert M.cache_payload_keys(tiny, EngineConfig()) == ("k", "v")
    assert M.cache_payload_keys(
        tiny, EngineConfig(kv_dtype="int8")) == ("k", "v", "ks", "vs")


def test_lagunas_parameters_and_outputs_are_what_they_were(monkeypatch):
    """The table the benchmark already had (full + sliding attention, a
    softmax router) through the code the new kinds were added to: the bytes
    of its rehearsal parameters at PRNGKey(7) are those of the commit before
    this PR (7912eaf), and one ``forward`` gives, bit for bit, what it gives
    with the router that commit had."""
    import hashlib

    cfg = _table_model(table="laguna")
    assert cfg.cache_kinds == ("kv",) and not cfg.has_seat_state
    eng = EngineConfig(num_blocks=16, attention_impl="einsum")
    params = M.init_params(jax.random.PRNGKey(7), cfg)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest()[:16] == "d2485d3ae570a4db"
    cache = M.init_cache(cfg, eng)
    assert sorted(cache) == ["k", "v"] and len(cache["k"]) == 5
    tok = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) + 300
    pos = jnp.tile(jnp.arange(6, dtype=jnp.int32), (2, 1))
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)

    def run():
        f = jax.jit(lambda p, c, t, po, b: M.forward(cfg, eng, p, c, t, po, b))
        return f(params, cache, tok, pos, bt)

    now = run()
    monkeypatch.setattr(moe, "route", _softmax_top_k)
    then = run()
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(then)):
        np.testing.assert_array_equal(a, b)


# ------------------------- what the engine decides --------------------------


def test_attention_choice_names_the_new_kinds(engine, monkeypatch):
    choice = engine.attention_impl_choice
    # the CPU leaves a chunk's latent attention to the einsum
    assert choice["latent"] == {"decode": "pallas-absorbed",
                                "spec": "einsum-expanded",
                                "prefill": "einsum-expanded"}
    # where kernels are compiled a chunk runs the tiled form (PR 54): the
    # largest bucket's tiles over the widest table; a spec window's few
    # rows stay the einsum's
    monkeypatch.setattr(M, "pallas_interpret", lambda mesh: False)
    assert M.attention_choice(
        engine.model_config, engine.config, None)["latent"] == {
            "decode": "pallas-absorbed", "spec": "einsum-expanded",
            "prefill": "pallas-tiled-expanded", "tiles": [64, 256]}
    assert M.latent_chunk_tiles(None, 512, 2048) == (512, 512)
    assert M.latent_chunk_tiles(None, 1, 2048) is None      # absorbed
    monkeypatch.undo()
    # KDA keeps short_conv's gather in its decode step too (PR 55)
    assert choice["linear"] == {"decode": "pallas-recurrent",
                                "prefill": "xla-chunked",
                                "conv": "xla-gather"}
    assert M.attention_choice(
        engine.model_config, EngineConfig(attention_impl="einsum"), None
    )["linear"]["decode"] == "xla-recurrent"
    # the walk's tile is the latent page's: one "KV head" 128 wide
    assert choice["tiles"]["decode"] == [1, 256]
    assert "latent" not in M.attention_choice(
        ModelConfig.tiny(), EngineConfig(), None)


def test_a_chunk_through_the_tiled_kernel_is_the_einsums(engine, monkeypatch):
    """``forward``'s wiring of ``ops/latent_chunk_attention.py``, which the
    CPU leaves to the einsum: the same chunk (behind a prefix, padded, a
    table twice the context) with the choice forced reads the same hidden
    states, and the trace notes the form with its tiles."""
    cfg, eng = engine.model_config, engine.config
    T, n, start = 64, 50, 70
    tok = jnp.asarray(np.random.default_rng(5).integers(
        1, cfg.vocab_size, (1, T)), jnp.int32)
    pos = jnp.where(jnp.arange(T) < n, start + jnp.arange(T), -1)[None]
    bt = jnp.arange(1, 17, dtype=jnp.int32)[None]

    def run():
        f = jax.jit(lambda p, c: M.forward(
            cfg, eng, p, c, tok, pos.astype(jnp.int32), bt,
            seats=jnp.zeros((1,), jnp.int32))[1])
        return np.asarray(f(engine.params, engine.cache))[:, :n]

    want = run()
    M.ATTENTION_TRACES.pop("latent_prefill", None)
    monkeypatch.setattr(
        M, "latent_chunk_tiles",
        lambda mesh, T, S: None if T == 1 else (T, 128))
    got = run()
    assert M.ATTENTION_TRACES.pop("latent_prefill") == {
        "impl": "pallas-tiled-expanded", "interpret": True,
        "tile": [64, 128]}
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _values(jaxpr, inside_kernels=False):
    """Every value a jaxpr names, nested jaxprs included; a Pallas kernel's
    body (what lives on chip) only where asked."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        if eqn.primitive.name == "pallas_call" and not inside_kernels:
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _values(sub, inside_kernels)


def test_no_score_array_leaves_the_kernel_at_agents_shapes():
    """T = 512, S = 2048, 64 heads (``longcat-flash-omni-ep32``): the
    einsum names values of ``heads x T x S`` elements (the scores, their
    mask, the weights); the tiled function names none outside its
    kernel."""
    from dynamo_tpu.ops.latent_chunk_attention import (
        chunk_tiles, latent_chunk_attention)

    H, T, S = 64, 512, 2048
    bf = jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    args = (sds((1, T, H, 128), bf), sds((1, T, H, 64), bf),
            sds((1, S, 640), bf), sds((512, H * 256), bf),
            sds((1, T), jnp.int32))
    cfg = types.SimpleNamespace(kv_lora_rank=512, qk_nope_head_dim=128,
                                qk_rope_head_dim=64, v_head_dim=128)
    einsum = jax.make_jaxpr(
        lambda qn, qp, ctx, w, pos: M.latent_attention(
            cfg, {"mla_wukv": w}, qn, qp, ctx, pos, absorbed=False))(*args)
    scores = [a for a in _values(einsum.jaxpr) if a.shape == (1, H, T, S)]
    assert len(scores) >= 4
    tiled = jax.make_jaxpr(
        lambda *a: latent_chunk_attention(
            *a, rank=512, rope=64, scale=192 ** -0.5,
            tiles=chunk_tiles(T, S)))(*args)
    sizes = [a.size for a in _values(tiled.jaxpr)]
    assert "pallas_call" in str(tiled) and max(sizes) < H * T * S // 8
    # and the largest thing on chip is a q tile by a key tile of one head
    assert max(a.size for a in _values(tiled.jaxpr, True)) < H * T * S // 8


# sha256 (16 hex) of the lowered T = 512 packed prefill program (W = 32) on
# the commit before PR 54 (401024f): a model that has no latent row runs
# nothing this PR touched
PREFILL_BEFORE_PR54 = {"tiny": "04a897115addd4e3",
                       "laguna": "ccb372fe1cfff844"}


@pytest.mark.parametrize("name", sorted(PREFILL_BEFORE_PR54))
def test_a_model_without_a_latent_row_lowers_its_chunk_as_before(name):
    import hashlib

    if name == "tiny":
        cfg = ModelConfig.tiny()
        eng = EngineConfig(num_blocks=96, max_model_len=1024)
    else:
        cfg = _table_model(True, name)
        eng = _table_engine_config(name, max_model_len=1024)
    assert not cfg.has_latent_cache
    T, W = 512, 32
    sds = jax.ShapeDtypeStruct
    text = M.make_packed_prefill_fn(cfg, eng, T, W, None).__wrapped__.lower(
        jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)),
        jax.eval_shape(lambda: M.init_cache(cfg, eng)),
        sds((eng.max_num_seqs + 1,), jnp.int32),
        sds((1, T + W + M.PP_SCALARS), jnp.int32),
        sds((2,), jnp.uint32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PREFILL_BEFORE_PR54[name]


def test_a_state_table_keeps_its_buckets_and_few_table_widths(engine):
    """The chunk's length is bucketed as for any model; the table's width
    in powers of four from the largest chunk's table, since every program
    is compiled when the engine is built."""
    assert engine.config.prefill_buckets == (64,)
    assert sorted(engine._packed_prefill_fns) == [(64, 4), (64, 16)]
    assert [engine._prefill_table_width(nb) for nb in (1, 4, 5, 16)] == [
        4, 4, 16, 16]
    width = InferenceEngine._prefill_table_width
    chip = types.SimpleNamespace(
        config=EngineConfig(num_blocks=61440, max_num_seqs=128,
                            max_model_len=8192), _seat_state=True)
    assert sorted({width(chip, nb) for nb in range(1, 513)}) == [32, 128, 512]
    assert [width(chip, nb) for nb in (32, 33, 128, 129)] == [
        32, 128, 128, 512]
    chip._seat_state = False
    assert [width(chip, nb) for nb in (1, 3, 33, 129)] == [1, 4, 64, 256]


def test_flops_count_the_new_kinds():
    cfg = _model(rehearse=False)
    fm = F.FlopsModel(cfg)
    # one latent layer attends the context, absorbed; six linear ones none
    assert fm.attn_coef == 2.0 * 32 * (2 * 512 + 64)
    assert fm.step_flops(1, 0) == (2.0 * F.active_param_count(cfg)
                                   + 6 * 7.0 * 32 * 128 * 128)
