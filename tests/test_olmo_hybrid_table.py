"""A table of gated-delta-rule and full-attention layers (PR 45): linear
layers whose float32 ``dk`` x ``dv`` states live in the scheduler's seats, 3:1
with no-rope full attention over K / V pages, every sublayer normed on its
way OUT — the tiny configuration of
``benchmarks/chip/configs/olmo-hybrid-7b-l8.json`` (``rehearse.model``: 8
layers, 6 heads, states of 8 x 16) against ``references/olmo_hybrid.py``.
CPU, float32; Pallas kernels interpreted.  The first table whose step reads
K / V pages through the paged kernel and seat states in one program."""

import asyncio
import dataclasses
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
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.observability import flops as F
from dynamo_tpu.observability.stepstats import DECODE, PREFILL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "olmo-hybrid-7b-l8"
SEED = 4500000417


def _file() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _model(rehearse: bool = True, **replace) -> ModelConfig:
    from benchmarks.chip import worker_launch as WL

    cfg = WL.model_config_from(_file(), rehearse)
    return dataclasses.replace(cfg, **replace) if replace else cfg


def _period(**replace) -> ModelConfig:
    """One whole period of the rehearsal model (3 linear layers + 1 full):
    what the engine-loop tests build, at half the compile."""
    cfg = _model()
    return dataclasses.replace(
        cfg, num_layers=4, layer_types=cfg.layer_types[:4],
        mlp_layer_types=cfg.mlp_layer_types[:4],
        num_heads_per_layer=cfg.num_heads_per_layer[:4], **replace)


def _reference():
    path = os.path.join(ROOT, "benchmarks", "chip", "references",
                        "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("olmo_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine_config(**kw) -> EngineConfig:
    # a table with seat state compiles every program when built: one bucket
    base = dict(num_blocks=96, max_model_len=256, max_num_batched_tokens=64,
                prefill_buckets=(64,), decode_buckets=(8,), max_num_seqs=8,
                pipeline_depth=1, attention_impl="pallas")
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(_model(), _engine_config(), seed=SEED)


# ------------------------- the configuration --------------------------------


def test_the_table_reads_the_published_keys():
    cfg = _model(rehearse=False)
    assert cfg.layer_types == (("linear_attention",) * 3
                               + ("full_attention",)) * 2
    assert cfg.cache_kinds == ("kv", "state")
    assert cfg.has_seat_state and cfg.gated_delta
    assert not cfg.has_latent_cache and not cfg.has_routed_experts
    assert [(k.name, k.num_heads, k.layers) for k in cfg.attn_kinds] == [
        ("linear_attention", 30, (0, 1, 2, 4, 5, 6)),
        ("full_attention", 30, (3, 7))]
    assert (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_allow_neg_eigval) == (
        96, 192, 4, True)
    assert (cfg.num_kv_heads, cfg.head_dim_, cfg.qk_norm,
            cfg.norm_placement) == (30, 128, "projection", "post")
    assert cfg.rope_of(cfg.attn_kinds[1]) == {"rope_type": "none"}
    assert hash(cfg) == hash(_model(rehearse=False))   # a jit static argument
    # the issue's count of what this chip holds: 2.435 B parameters
    assert F.param_count(cfg) == 2435748072


def test_every_published_number_is_the_sources():
    """The catalog's config for ``Olmo-Hybrid-7B``: every number is in the
    file under its key; ``num_hidden_layers`` alone is reduced, and
    ``layer_types`` is the source's first eight."""
    source = {
        "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4}
    cfg = _file()
    assert sorted(cfg["reduced"]) == ["num_hidden_layers"]
    for key, value in source.items():
        if key in cfg["reduced"]:
            assert cfg["reduced"][key]["source"] == value
            assert cfg["reduced"][key]["here"] == cfg[key] == 8
        else:
            assert cfg[key] == value, key
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert cfg["linear_allow_neg_eigval"] is True
    assert cfg["attention_bias"] is False and cfg["hidden_act"] == "silu"
    assert cfg["tie_word_embeddings"] is False
    for key in ("norm_placement", "qk_norm", "rope", "linear_inputs",
                "linear_decay", "linear_beta", "linear_state", "linear_gate",
                "linear_draws", "torch_dtype", "head_dim"):
        assert cfg["assumed"][key]
    assert cfg["deployment"] and cfg["rehearse"]["model"]
    # worst case of the cell: 96 clients of 1280 + 768 tokens
    args = dict(zip(cfg["engine_args"][::2], cfg["engine_args"][1::2]))
    worst = 96 * -(-(1280 + 768) // int(args["--block-size"]))
    assert worst == 12288 <= int(args["--num-blocks"]) - 1


@pytest.mark.parametrize("change,names", [
    # a linear rule without its head widths
    ({"linear_key_head_dim": 0}, ["softplus_head", "linear_key_head_dim"]),
    ({"linear_value_head_dim": 0}, ["linear_value_head_dim"]),
    ({"linear_conv_kernel_dim": 0}, ["linear_conv_kernel_dim"]),
    # Kimi Delta Attention given the other rule's fields
    ({"linear_decay": "", "norm_placement": "pre"},
     ["linear_*", "softplus_head"]),
    ({"linear_decay": "sigmoid"}, ["linear_decay", "sigmoid"]),
    ({"linear_gate": "tanh"}, ["linear_gate", "tanh"]),
    # a K / V kind with neither a rope nor an explicit none
    ({"rope_parameters": None}, ["rope_parameters", "full_attention",
                                 "none"]),
    ({"qk_norm": "per-head"}, ["qk_norm", "per-head"]),
    ({"norm_placement": "sandwich"}, ["norm_placement", "sandwich"]),
])
def test_a_half_specified_table_is_refused(change, names):
    with pytest.raises(ValueError) as e:
        _model(**change)
    for name in names:
        assert name in str(e.value)


def test_kda_keeps_its_rule_and_refuses_a_post_norm():
    from test_layer_table import _model as table_model

    ling = table_model(True, "ling")
    assert not ling.gated_delta and ling.norm_placement == "pre"
    with pytest.raises(ValueError, match="norm_placement 'post'"):
        dataclasses.replace(ling, norm_placement="post")


def test_parameters_and_cache_follow_the_kinds(engine):
    cfg, eng = engine.model_config, engine.config
    layers = engine.params["layers"]
    D, H, hd, dk, dv = 64, 6, 16, 8, 16
    assert layers["wq"]["linear_attention"].shape == (6, D, H * dk)
    assert layers["wo"]["linear_attention"].shape == (6, H * dv, D)
    assert layers["wq"]["full_attention"].shape == (2, D, H * hd)
    assert layers["wk"].shape == layers["wv"].shape == (2, D, H * hd)
    assert layers["k_norm"].shape == (2, H * hd)
    assert layers["q_norm"]["full_attention"].shape == (2, H * hd)
    assert layers["gdn_wk"].shape == (6, D, H * dk)
    assert layers["gdn_wv"].shape == layers["gdn_wg"].shape == (6, D, H * dv)
    assert layers["gdn_wa"].shape == layers["gdn_wb"].shape == (6, D, H)
    assert layers["gdn_conv"].shape == (6, 4, H * (2 * dk + dv))
    assert layers["gdn_a_log"].dtype == jnp.float32
    assert layers["gdn_dt_bias"].shape == (6, H)
    assert not [k for k in layers if k.startswith(("kda_", "mla_"))]
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(engine.params))
    assert n == F.param_count(cfg)
    S = eng.max_num_seqs
    assert sorted(engine.cache) == ["conv", "k", "state", "v"]
    assert len(engine.cache["k"]) == 2 and len(engine.cache["state"]) == 6
    assert engine.cache["k"][0].shape == (eng.num_blocks, H, 16, hd)
    # 16 values a head: no group of the 6 heads fills a lane tile
    assert engine.cache["state"][0].shape == (S + 1, H, dk, dv)
    assert engine.cache["state"][0].dtype == jnp.float32
    assert engine.cache["conv"][0].shape == (S + 1, 3, H * (2 * dk + dv))
    real = jax.eval_shape(lambda: M.init_cache(
        _model(rehearse=False), EngineConfig(num_blocks=16, max_num_seqs=96)))
    assert real["state"][0].shape == (97, 15, 96, 384)
    assert real["conv"][0].shape == (97, 3, 11520)
    assert real["k"][0].shape == (16, 30, 16, 128)


def test_attention_choice_names_the_rule_and_the_full_kind(engine):
    choice = engine.attention_impl_choice
    assert choice["linear"] == {"decode": "pallas-recurrent",
                                "prefill": "xla-chunked",
                                "conv": "pallas-seats",
                                "rule": "gated-delta"}
    assert choice["impl"]["decode"] == "pallas"
    assert choice["impl"]["prefill"] == "einsum"
    assert "latent" not in choice
    xla = M.attention_choice(
        engine.model_config, EngineConfig(attention_impl="einsum"), None
    )["linear"]
    assert (xla["decode"], xla["conv"]) == ("xla-recurrent", "xla-gather")
    assert engine.device_report()["attention_choice"]["linear"] == (
        choice["linear"])


def _lowered(cfg, eng, program):
    """The StableHLO of a step program of ``cfg`` with the ops' names: the
    8-row decode window, or the ``T = 64`` packed prefill."""
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, eng))
    Wcap, S = eng.max_blocks_per_seq, eng.max_num_seqs

    def SD(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    if program == "window":
        ctl = jax.eval_shape(lambda: M.init_ctl(eng, S, Wcap))
        window, _ = M.make_autopilot_fns(cfg, eng, Wcap, None)
        low = window.__wrapped__.lower(params, cache, ctl,
                                       SD((8,), jnp.int32))
    else:
        T, W = 64, 4
        fn = M.make_packed_prefill_fn(cfg, eng, T, W, None)
        low = fn.__wrapped__.lower(
            params, cache, SD((S + 1,), jnp.int32),
            SD((1, T + W + M.PP_SCALARS), jnp.int32), SD((2,), jnp.uint32))
    return low.as_text(debug_info=True)


# short_conv's tail: a slice a row at the row's own offset, which lowers to
# a gather under the layer's conv scope; at 128 rows the chip runs it as a
# loop of 128 trips a layer (PR 55)
ROW_GATHER = "gdn_conv/vmap()/gather"


@pytest.mark.parametrize("impl,conv", [("pallas", "pallas-seats"),
                                       ("einsum", "xla-gather")])
def test_the_conv_form_named_is_the_form_traced(impl, conv):
    """``attention_choice()["linear"]["conv"]`` against the lowered decode
    window: where decode runs its kernels the window holds the kernel
    ``gdn_conv_step`` and no gather with an offset a row under ``gdn_conv``;
    on the XLA path it holds ``short_conv``'s and no such kernel.  A chunk
    (``T > 1``) keeps ``short_conv`` either way."""
    cfg, eng = _period(), _engine_config(attention_impl=impl)
    assert M.attention_choice(cfg, eng, None)["linear"]["conv"] == conv
    window = _lowered(cfg, eng, "window")
    assert ("gdn_conv_step" in window) == (conv == "pallas-seats")
    assert (ROW_GATHER in window) == (conv == "xla-gather")
    chunk = _lowered(cfg, eng, "prefill")
    assert ROW_GATHER in chunk and "gdn_conv_step" not in chunk


def test_flops_count_the_rule():
    cfg = _model(rehearse=False)
    fm = F.FlopsModel(cfg)
    assert fm.attn_coef == 2 * 4.0 * 128 * 30       # two full layers
    assert fm.step_flops(1, 0) == (2.0 * F.active_param_count(cfg)
                                   + 6 * 7.0 * 30 * 96 * 192)


# ------------------------- against the reference ----------------------------


def test_chunked_prefill_and_kernel_decode_match_the_reference(engine):
    """``forward`` in chunks of 64 into K / V pages and two seats of a
    state pool (the last chunk ragged), then the decode path with both
    Pallas kernels interpreted: logits, the seats' states, the convolution
    tails and a K / V page against the plain float32 forward.  float32
    against float32 at ``highest``: what is left is the order of the sums."""
    ref = _reference()
    out = ref.compare(engine, SEED, T=150, chunk=64, n_decode=6)
    assert out["ok"], out
    assert out["decode_attention"]["impl"] == "pallas"
    for phase in ("prefill", "decode"):
        assert out[phase]["rel"] < 2e-4, (phase, out[phase])
    assert out["state"]["rms_rel_max"] < 1e-4
    assert out["state"]["stray_max"] == 0.0
    assert out["conv"]["rms_rel_max"] < 1e-4
    assert out["kv"]["rms_rel"] < 1e-4
    assert out["probes"] == [0, 1, 2, 3, 63, 64, 65, 66, 67, 127, 128, 129,
                             130, 131, 149]


def test_an_undoubled_step_size_is_seen(engine):
    """The reference broken on purpose (``beta`` left in (0, 1)): the served
    path, which doubles it, reads far from it in the logits and the state."""
    ref = _reference()
    out = ref.compare(engine, SEED, T=150, chunk=64, n_decode=6,
                      variant="beta_undoubled")
    assert not out["ok"]
    assert out["state"]["rms_rel_first"] > 0.05
    assert out["both"]["rms_rel"] > 0.05


def test_a_state_kept_in_bfloat16_is_seen_in_the_state():
    """The pool in bfloat16 (the decode step falls back from the kernel to
    the XLA recurrence): against the float32 engine the state parts by a
    rounding a token."""
    ref = _reference()
    eng = InferenceEngine(_period(state_dtype="bfloat16"), _engine_config(),
                          seed=SEED)
    assert eng.cache["state"][0].dtype == jnp.bfloat16
    out = ref.compare(eng, SEED, T=150, chunk=64, n_decode=6)
    assert out["state"]["rms_rel_first"] > 1e-3
    assert out["state"]["stray_max"] == 0.0


# ------------------------- through the engine -------------------------------


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


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(256, 512, size=n)] for n in lengths]


def _reference_logits(cfg, params, prompt, toks):
    """The reference's logits at the positions the engine sampled from."""
    ref = _reference()
    full = np.asarray(prompt + toks[:-1], np.int32)
    hidden = ref.reference_hidden(cfg, params, full)[0]
    return np.asarray(ref.head_logits(cfg, params, hidden[len(prompt) - 1:]))


def test_the_engine_serves_the_reference_and_counts_its_seats(monkeypatch):
    """Store-less engine loop: packed prefill chunks by seat, then the
    decode window over seats and pages in one program.  The token the
    engine chose at each step is within rounding of the reference's best
    logit there (logits, not tokens: with random weights the largest logit
    changes on the order of a sum); the records carry ``state_rows`` and
    ``context_sum``; the trash seat and block 0 are zeros."""
    monkeypatch.setenv("DYNTPU_OBS_WINDOW_S", "3600")
    cfg = _period()
    eng = InferenceEngine(cfg, _engine_config(), seed=SEED)
    assert sorted(eng._packed_prefill_fns) == [(64, 4), (64, 16)]
    prompts = _prompts((70, 49, 61))
    got = _run(eng, prompts, [9, 4, 7])
    records = list(eng.obs._records)
    for p, toks in zip(prompts, got):
        logits = _reference_logits(cfg, eng.params, p, toks)
        best = logits.max(-1)
        chosen = logits[np.arange(len(toks)), toks]
        assert np.all(best - chosen <= 1e-4 * np.abs(logits).max()), (
            best - chosen)
    decode = [r for r in records if r.kind == DECODE]
    prefill = [r for r in records if r.kind == PREFILL]
    assert decode and prefill
    assert all(r.state_rows == r.live_rows for r in decode)
    assert all(r.context_sum > 0 and r.latent_context_sum == 0
               for r in decode)
    assert all(r.state_rows == 1 for r in prefill)
    # no latent row: nothing walked, nothing gathered for one
    assert all(r.latent_keys_walked == r.latent_keys_gathered == 0
               for r in prefill)
    S = eng.config.max_num_seqs
    for key in ("state", "conv"):
        for layer in eng.cache[key]:
            np.testing.assert_array_equal(layer[S], 0)
    for key in ("k", "v"):
        np.testing.assert_array_equal(eng.cache[key][0][0], 0)
    assert any(float(jnp.abs(layer[:S]).max()) > 0
               for layer in eng.cache["state"])


def test_the_kernel_path_and_the_xla_path_serve_the_same():
    """The same requests through an engine whose decode runs its kernels
    (the recurrence and the conv step, interpreted) and through one on the
    XLA path: the same tokens; the first layer's conv tails to the bit (a
    tail is the layer's own inputs, whatever form moved them, and the first
    layer's come from the tokens' embeddings alone); the later layers'
    tails and every state within the order of the recurrence's sums."""
    prompts = _prompts((70, 49, 61), seed=3)
    caches, tokens = [], []
    for impl in ("pallas", "einsum"):
        eng = InferenceEngine(_period(), _engine_config(attention_impl=impl),
                              seed=SEED)
        tokens.append(_run(eng, prompts, [9, 4, 7]))
        caches.append(eng.cache)
    assert tokens[0] == tokens[1]
    kernel, xla = caches
    assert any(float(jnp.abs(t).max()) > 0 for t in kernel["conv"])
    np.testing.assert_array_equal(np.asarray(kernel["conv"][0]),
                                  np.asarray(xla["conv"][0]))
    for key in ("conv", "state"):
        for a, b in zip(kernel[key], xla[key]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=1e-5)


def test_a_seat_handed_to_a_new_sequence_starts_from_zeros():
    """One seat (``max_num_seqs`` 1): the second request takes the seat the
    first left its state and convolution tail in, and reads as a fresh
    engine does."""
    prompts = _prompts((40, 33), seed=1)
    eng = InferenceEngine(_period(), _engine_config(max_num_seqs=1),
                          seed=SEED)
    got = _run(eng, prompts, [5, 6])
    # a fresh engine gives each prompt a seat of its own that nobody held
    fresh = InferenceEngine(_period(), _engine_config(), seed=SEED)
    assert got == _run(fresh, prompts, [5, 6])


def test_a_preempted_sequence_recomputed_from_its_first_token_is_the_same(
        monkeypatch):
    """A pool too small for both sequences to grow: one is preempted by
    recompute, prefilled again from its first token (its seat's state reset
    at position 0, its pages written again) and continues where it was."""
    calls = []
    real = Scheduler.preempt_recompute

    def spy(self, seq):
        calls.append(seq.seq_id)
        return real(self, seq)

    monkeypatch.setattr(Scheduler, "preempt_recompute", spy)
    prompts = _prompts((40, 40), seed=2)
    eng = InferenceEngine(_period(), _engine_config(num_blocks=9,
                                                    watermark=0.0),
                          seed=SEED)
    got = _run(eng, prompts, [30, 30], gap=0.05)
    assert calls, "the pool never ran dry: no preemption was tested"
    calls.clear()
    fresh = InferenceEngine(_period(), _engine_config(), seed=SEED)
    assert got == _run(fresh, prompts, [30, 30])
    assert not calls


def test_no_prefix_hit_and_nothing_moves_the_state():
    """A model with seat state takes no prefix hit, and what moves K / V
    pages alone refuses it when built."""
    eng = InferenceEngine(_period(), _engine_config(), seed=SEED)
    assert eng.scheduler.seat_state
    assert eng._kv_extract is None and eng._kv_inject is None
    with pytest.raises(ValueError, match="K and V pages only"):
        M.refuse_unpaged(eng.model_config, "KVBM")
    with pytest.raises(ValueError, match="kv-dtype"):
        InferenceEngine(_period(), _engine_config(kv_dtype="int8"), seed=SEED)
    asyncio.run(eng.stop())


# ------------------------- the cell's readers -------------------------------


def _reader(name):
    from benchmarks.chip import run as R

    return R.load_reader(name)


def test_the_new_readers_count_the_issues_bytes_and_flops():
    cfg = _file()
    assert _reader("gdn_state_roofline").state_bytes(96, 6, cfg) == (
        96 * 6 * 2 * 30 * 96 * 192 * 4)                  # 2.55 GB a step
    assert _reader("attn_full_roofline").kv_bytes_attended(1, cfg) == (
        2 * 15360)                                       # two full layers
    # a token a head: 2 * 64 * (3 * 96 + 2 * 192) + 6 * 96 * 192
    assert _reader("gdn_chunk_roofline").chunk_flops(1, 6, cfg) == (
        6 * 30 * 196608)


@pytest.mark.parametrize("name", ["gdn_step_dev_ms", "gdn_state_roofline",
                                  "gdn_chunk_roofline",
                                  "attn_full_roofline"])
def test_a_new_reader_finds_nothing_where_nothing_is(name):
    """A program without the scopes, the counters or the capture (the
    parent; a cell of another configuration) gives None and does not
    raise."""
    reader = _reader(name)
    assert (reader.SOURCE, reader.MOVES) == ("device_trace", "tpot_p50_ms")
    bare = {"trace": None, "peaks": {"hbm_bytes_per_s": 819e9,
                                     "bf16_flops": 197e12},
            "steps": [], "config": _file(), "rehearse": False, "chips": 1,
            "window": (0.0, 1.0), "health_end": {}}
    assert reader.read(bare) is None
    steps = [{"kind": "decode", "state_rows": 96, "context_sum": 10 ** 5},
             {"kind": "prefill", "state_rows": 1, "real_tokens": 500}]
    assert reader.read({**bare, "steps": steps}) is None
    from benchmarks.chip import run as R

    dense = {**bare, "steps": steps,
             "config": R.load_config("mistral-7b-v0.3-l16"),
             "trace": {"window_s": 1.0, "busy_s": 1.0,
                       "device_ops": [["paged_attention_ragged", 0.5]]}}
    assert reader.read(dense) is None


def test_attn_full_roofline_on_a_hand_made_trace():
    reader = _reader("attn_full_roofline")
    cfg = _file()
    ctx = {"trace": {"window_s": 1.0, "busy_s": 1.0,
                     "device_ops": [["paged_attention_ragged", 0.5]]},
           "peaks": {"hbm_bytes_per_s": 819e9}, "window": (0.0, 1.0),
           "steps": [{"kind": "decode", "context_sum": 10 ** 7}],
           "config": cfg, "chips": 1}
    want = 100.0 * 2 * 15360 * 1e7 / 819e9 / 0.5
    assert reader.read(ctx) == pytest.approx(want)
    assert reader.read(ctx) < 100.0
