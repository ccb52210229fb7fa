"""What only the table of ``longcat-flash-omni-ep32.json`` has (PR 41), at
the size of its ``rehearse.model`` (hidden 64, 4 heads, 16 routed + 8
zero-compute experts of which 4 routed are held, 4 a token, two double
layers = four rows), float32, seeded, on the CPU with Pallas interpreted:

1. the double layer: a sparse row leaves its routed sum to the dense row
   behind it, whose attention and FFN never see it;
2. the router over routed and zero-compute outputs: identities add ``w * x``,
   reach no grouped matmul, and are counted; the shares add up;
3. the latent attention with a q-LoRA and the two scales: absorbed =
   expanded = paged kernel = ``references/longcat.py``;
4. the served path against the reference, the router's precision, the
   engine's counters, the two new readers.

What holds for any table runs for this one in ``test_layer_table.py``
(``TABLES["longcat"]``)."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request
from dynamo_tpu.observability import flops as F
from dynamo_tpu.observability.stepstats import DECODE
from dynamo_tpu.parallel import moe

from test_layer_table import TABLES, _engine_config as _table_engine_config
from test_layer_table import _model as _table_model
from test_layer_table import _poisoned_gmm
from test_layer_table import _reference as _table_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = TABLES["longcat"]["seed"]
STAT = {name: i for i, name in enumerate(moe.MOE_STATS)}


def _model(rehearse: bool = True, **replace) -> ModelConfig:
    return _table_model(rehearse, "longcat", **replace)


def _reference():
    return _table_reference("longcat")


def _engine_config(**kw) -> EngineConfig:
    return _table_engine_config("longcat", **kw)


def _reader(name: str):
    path = os.path.join(ROOT, "benchmarks", "chip", "layer_metrics",
                        name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(_model(), _engine_config(), seed=SEED)


# ------------------------- the configuration --------------------------------


def test_the_table_reads_the_published_keys():
    cfg = _model(rehearse=False)
    assert [(k.name, k.num_heads, k.layers) for k in cfg.attn_kinds] == [
        ("mla_attention", 64, tuple(range(8)))]
    assert [(e.attn_at, e.ffn, e.ffn_at) for e in cfg.layer_table] == [
        (0, "sparse", 0), (1, "dense", 0), (2, "sparse", 1), (3, "dense", 1),
        (4, "sparse", 2), (5, "dense", 2), (6, "sparse", 3), (7, "dense", 3)]
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.zero_expert_num) == (
        1536, 512, 256)
    assert cfg.router_width == 768 and cfg.experts_held == (0, 16)
    assert cfg.moe_shortcut and not cfg.norm_topk_prob
    assert cfg.cache_kinds == ("latent",) and not cfg.has_seat_state
    assert cfg.intermediate_size == cfg.shared_expert_intermediate_size \
        == 12288
    assert hash(cfg) == hash(_model(rehearse=False))


@pytest.mark.parametrize("change,match", [
    (dict(mlp_layer_types=("sparse", "sparse", "dense", "dense")),
     "moe_shortcut"),
    (dict(mlp_layer_types=("dense", "sparse", "dense", "sparse")),
     "moe_shortcut"),
    (dict(zero_expert_type="constant"), "identities"),
    (dict(n_group=4, topk_group=2), "group limit"),
])
def test_a_double_layer_that_contradicts_itself_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        _model(**change)


def test_parameters_and_cache_follow_the_double_layer(engine):
    cfg, L = engine.model_config, engine.params["layers"]
    assert L["wq"]["mla_attention"].shape == (4, 48, 4 * 24)     # Wqb
    assert L["mla_wqa"].shape == (4, 64, 48)
    assert L["mla_q_norm"].shape == (4, 48)
    assert L["w_router"].shape == (2, 64, 24)                     # 16 + 8
    assert L["router_bias"].shape == (2, 24)
    # a softmax's bias lies at the scale of its scores, 1 / 24 here
    assert 0 < float(jnp.abs(L["router_bias"]).max()) < 4 * 0.25 / 24
    assert L["shared_gate"].shape == L["w_gate"].shape == (2, 64, 128)
    assert [a.shape for a in L["expert_gate"]] == [(4, 64, 32)] * 2
    assert "w_attn_gate" not in L and "wk" not in L
    assert sorted(engine.cache) == ["latent"]
    assert [p.shape for p in engine.cache["latent"]] == [
        (engine.config.num_blocks, 1, 16, 128)] * 4
    assert F.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(engine.params))


def test_flops_count_a_q_lora_and_a_double_layer():
    cfg = _model(rehearse=False)
    D, H = 6144, 64
    mla = (D * 1536 + 1536 + 1536 * H * 192 + D * 576 + 512
           + 512 * H * 256 + H * 128 * D)
    dense = 3 * D * 12288
    double = 2 * mla + 2 * dense + 4 * D + D * 768 + 768
    expert = 3 * D * 2048
    assert F.param_count(cfg) == (4 * double + 4 * 16 * expert
                                  + 2 * 16384 * D + D)
    assert round(F.param_count(cfg) / 1e9, 2) == 5.17     # ISSUE.md's 5.17 B
    # of a token's 12 choices 16 / 768 fall on a held expert on average
    assert F.active_param_count(cfg) == (
        4 * (double - 768) + int(12 * 16 / 768 * expert) * 4
        + 16384 * D + D)
    fm = F.FlopsModel(cfg)
    assert fm.attn_coef == 8 * 2.0 * H * (2 * 512 + 64)


# ------------------------- (1) the double layer -----------------------------


def _one_double_layer(engine, scale_down: float = 1.0):
    """The engine's first double layer alone as a model of two rows, its
    parameters (the held experts' down projections times ``scale_down``), a
    cache of its own, and a chunk of 24 tokens through ``forward``."""
    cfg = dataclasses.replace(
        engine.model_config, num_layers=2,
        layer_types=("mla_attention",) * 2,
        mlp_layer_types=("sparse", "dense"), num_heads_per_layer=(4, 4))
    P = engine.params
    two = jax.tree.map(lambda a: a[:2], {
        k: v for k, v in P["layers"].items()
        if k not in M.EXPERT_LEAVES})
    for k in ("w_router", "router_bias", "shared_gate", "shared_up",
              "shared_down", "w_gate", "w_up", "w_down"):
        two[k] = P["layers"][k][:1]
    for k in M.EXPERT_LEAVES:
        two[k] = [P["layers"][k][0] * (scale_down if k == "expert_down"
                                       else 1.0)]
    params = dict(P, layers=two)
    eng = dataclasses.replace(engine.config, num_blocks=8)
    toks = np.random.default_rng(7).integers(1, 512, size=(1, 24))
    pos = np.arange(24, dtype=np.int32)[None]
    tables = np.asarray([[1, 2, 0, 0]], np.int32)
    stats = []
    cache, h = M.forward(cfg, eng, params, M.init_cache(cfg, eng),
                         jnp.asarray(toks, jnp.int32), jnp.asarray(pos),
                         jnp.asarray(tables), moe_stats=stats)
    return cfg, cache, h, stats


def test_the_routed_sum_does_not_reach_the_second_attention(engine):
    """Row B's attention writes its latent from ``n_1(h2)``: with the held
    experts' outputs tripled the page is bit for bit what it was (``s`` has
    not joined yet), and the layer's output is not."""
    _, cache1, h1, stats = _one_double_layer(engine)
    _, cache3, h3, _ = _one_double_layer(engine, scale_down=3.0)
    assert int(stats[0][STAT["moe_pairs_held"]]) > 0
    for a, b in zip(cache1["latent"], cache3["latent"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(h3 - h1).max()) > 1e-3


def test_one_double_layer_is_the_references(engine):
    """Two rows of the table = one published double layer of the reference
    (written as such, not as rows)."""
    cfg, _, h, _ = _one_double_layer(engine)
    P = engine.params
    two = {k: (v if k in M.EXPERT_LEAVES else
               jax.tree.map(lambda a: a[:2], v))
           for k, v in P["layers"].items()}
    toks = np.random.default_rng(7).integers(1, 512, size=(24,))
    want, routing, _ = _reference().reference_hidden(
        cfg, dict(P, layers=two), toks)
    assert len(routing) == 1
    np.testing.assert_allclose(np.asarray(h)[0], np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_a_shortcut_table_is_run_by_forward_alone(engine):
    cfg = engine.model_config
    p, entry, _ = M.layer_params(cfg, engine.params["layers"], 0)
    h = jnp.zeros((1, 4, cfg.hidden_size))
    with pytest.raises(ValueError, match="moe_shortcut"):
        M.ffn(cfg, entry, p, h, interpret=True)


# ------------------------- (2) the router's zero-compute outputs ------------


def _experts(E=16, Z=8, D=24, Fe=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),  # noqa
                               jnp.float32)
    x = jnp.asarray(rng.standard_normal((40, D)), jnp.float32)
    bias = jnp.asarray(0.25 / (E + Z) * rng.standard_normal(E + Z),
                       jnp.float32)
    return x, f(D, E + Z) * np.sqrt(D), f(E, D, Fe), f(E, D, Fe), \
        f(E, Fe, D), bias


KW = dict(top_k=4, scale=6.0, renormalise=False, n_zero=8, interpret=True)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """16 routed + 8 zero-compute experts over 4 shares: the routed parts of
    all four shares, the identity part counted once and not four times,
    equal the reference's expert layer over all 16; every share's counters
    part its pairs into held, identity and not held."""
    ref = _reference()
    x, wr, wg, wu, wd, bias = _experts()
    live = jnp.asarray(np.arange(40) % 9 != 4)
    total, zero_part = 0.0, None
    for shard in range(4):
        lo = 4 * shard
        out, stats, chosen = moe.routed_ffn(
            x, wr, wg[lo:lo + 4], wu[lo:lo + 4], wd[lo:lo + 4],
            held_start=lo, bias=bias, live=live, **KW)
        total = total + out
        ch = np.asarray(chosen)[np.asarray(live)]
        pairs, held, zero = (int(stats[STAT[k]]) for k in (
            "moe_pairs", "moe_pairs_held", "moe_pairs_zero"))
        assert pairs == ch.size and zero == int((ch >= 16).sum())
        assert held == int(((ch >= lo) & (ch < lo + 4)).sum())
        not_held = int(((ch < 16) & ((ch < lo) | (ch >= lo + 4))).sum())
        assert zero + held + not_held == pairs
    assert 0.15 < zero / pairs < 0.65       # 8 of 24 outputs: a third or so
    with jax.default_matmul_precision("highest"):
        weight, _, flipped, short, strangers = ref.router_weights(
            x, wr, bias, top_k=4, scale=6.0, n_routed=16, forced=chosen)
        zero_part = jnp.sum(weight[:, 16:], -1, keepdims=True) * x
        want = zero_part + sum(
            weight[:, e:e + 1] * ref.swiglu(x, wg[e], wu[e], wd[e])
            for e in range(16))
    assert not bool(flipped.any()) and int(strangers.sum()) == 0
    keep = np.asarray(live)[:, None]
    np.testing.assert_allclose(np.asarray(total - 3 * zero_part) * keep,
                               np.asarray(want) * keep, atol=3e-5, rtol=1e-4)
    assert np.all(np.asarray(total)[~np.asarray(live)] == 0)
    # not renormalised: the weights are 6 p, whatever their sum
    assert float(jnp.std(jnp.sum(weight, -1))) > 0


def test_a_token_of_identities_alone_adds_no_row_to_a_group(monkeypatch):
    """Half the tokens are pushed onto the zero-compute outputs with all
    four choices: each gets ``(sum w) x``, the grouped matmuls see no row of
    theirs (poisoned rows behind the groups reach nothing), and where every
    token is such a token no expert is touched at all."""
    x, wr, wg, wu, wd, _ = _experts(seed=2)
    bias = None                  # the choice follows the scores alone
    to_zero = np.arange(40) % 2 == 0
    x = x.at[:, 0].set(jnp.where(to_zero, 30.0, -30.0))
    wr = wr.at[0].set(0.0).at[0, 16:].set(1.0)   # +-30 on those logits alone
    import jax.experimental.pallas.ops.tpu.megablox.gmm  # noqa: F401

    _poisoned_gmm(monkeypatch)
    out, stats, chosen = moe.routed_ffn(x, wr, wg[:4], wu[:4], wd[:4],
                                        held_start=0, bias=bias, **KW)
    ch = np.asarray(chosen)
    assert np.all(ch[to_zero] >= 16) and np.all(ch[~to_zero] < 16)
    assert np.all(np.isfinite(np.asarray(out)))
    p = jax.nn.softmax(jnp.dot(x, wr, precision="highest"), axis=-1)
    w = 6.0 * jnp.take_along_axis(p, chosen, axis=1)
    np.testing.assert_allclose(
        np.asarray(out)[to_zero],
        np.asarray(jnp.sum(w, -1, keepdims=True) * x)[to_zero], rtol=1e-5)
    assert int(stats[STAT["moe_pairs_zero"]]) == 4 * int(to_zero.sum())
    held = int(stats[STAT["moe_pairs_held"]])
    assert held == int((ch[~to_zero] < 4).sum())
    # all tokens identities: nothing held, nothing touched, no load
    out, stats, _ = moe.routed_ffn(
        x.at[:, 0].set(30.0), wr, wg[:4], wu[:4], wd[:4], held_start=0,
        bias=bias, **KW)
    assert [int(v) for v in stats] == [160, 0, 0, 160, 0]
    assert np.all(np.isfinite(np.asarray(out)))


def test_without_zero_experts_the_layer_is_what_it_was():
    x, wr, wg, wu, wd, _ = _experts(Z=0)
    kw = dict(KW, n_zero=0)
    out, stats, _ = moe.routed_ffn(x, wr, wg[:8], wu[:8], wd[:8],
                                   held_start=0, **kw)
    assert int(stats[STAT["moe_pairs_zero"]]) == 0
    assert out.dtype == x.dtype


def test_scores_kept_in_bfloat16_are_seen_by_the_routers_check(engine):
    """The program's router on the reference's own float32 inputs agrees
    with the reference's to rounding; a reference whose scores are rounded
    to bfloat16 stands 2^-9 to 2^-8 from it, which no logit shows."""
    ref = _reference()
    kw = dict(T=150, chunk=64, n_decode=2)
    sound = ref.compare(engine, SEED, **kw)
    low = ref.compare(engine, SEED, variant="router_bf16", **kw)
    assert sound["router"]["weight_rel_max"] < 1e-5
    assert sound["router"]["set_mismatch"] == 0
    assert 2.0 ** -10 < low["router"]["weight_rel_max"] < 2.0 ** -7
    assert low["both"]["rms_rel"] < 0.01 and low["ok"]
    assert sound["routing"]["zero_strangers"] == 0
    share = (sound["routing"]["served_zero_pairs"]
             / sound["routing"]["served_pairs"])
    assert 0.2 < share < 0.45


def test_the_uncut_model_is_the_references(engine):
    """All 16 routed experts held (``expert_shard`` 0 of 1): the served path
    against the reference's uncut double layers."""
    cfg = _model(expert_shard={"index": 0, "of": 1}, num_experts=16)
    eng = InferenceEngine(cfg, _engine_config(), seed=SEED + 1)
    v = _reference().compare(eng, SEED + 1, T=70, chunk=32, n_decode=3)
    assert v["ok"] and v["both"]["rms_rel"] < 1e-4, v
    assert v["routing"]["flipped"] == 0


# ------------------------- (3) the latent attention -------------------------


def test_absorbed_expanded_kernel_and_reference_agree(engine):
    """The q-LoRA MLA with both scales (sqrt(64 / 48) and sqrt(64 / 32)
    here): expanded = absorbed = the paged kernel = the reference's."""
    cfg = engine.model_config
    p, _, kind = M.layer_params(cfg, engine.params["layers"], 1)
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
    bs = 16
    plane = jnp.zeros((1 + B * 3, 1, bs, 128)).at[1:].set(
        ctx.reshape(B * 3, bs, 128)[:, None])
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    got = M._paged_latent_decode(
        cfg, engine.config, None, p, q_nope[:, -1:], q_pe[:, -1:], plane,
        tables, jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(got[:, 0], full[:, -1], atol=2e-5, rtol=1e-4)
    ref = _reference()
    L = engine.params["layers"]
    kw = dict(heads=4, rank=32, q_rank=48, nope=16, rot=8, vdim=16,
              theta=1e7, eps=cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(h[0], L["attn_norm"][1],
                                    ref.attention_leaves(L, 1),
                                    scale_q=True, scale_kv=True, **kw)
        bare = ref.latent_attention(h[0], L["attn_norm"][1],
                                    ref.attention_leaves(L, 1),
                                    scale_q=False, scale_kv=False, **kw)
    out = full[0].reshape(S, -1) @ p["wo"]
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=1e-4)
    assert float(jnp.abs(want - bare).max()) > 1e-2      # the scales matter


# ------------------------- (4) the engine, the counters, the readers --------


@pytest.mark.anyio
async def test_the_engine_serves_the_reference_and_counts_identities():
    """Through scheduler, chunked prefill and the decode window: greedy
    tokens are the reference's own, and every decode record parts its pairs
    into held, identity and the rest, with all four planes' walk counted as
    one plane's."""
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
        hidden, _, _ = ref.reference_hidden(cfg, eng.params, full)
        logits = np.asarray(ref.head_logits(cfg, eng.params,
                                            hidden[len(p) - 1:]))
        assert toks == [int(t) for t in logits.argmax(-1)]
    decode = [r for r in records if r.kind == DECODE]
    assert decode
    k, doubles = cfg.num_experts_per_token, 2
    for r in decode:
        assert r.moe_pairs == r.live_rows * k * doubles
        assert 0 <= r.moe_pairs_held and 0 <= r.moe_pairs_zero
        assert r.moe_pairs_held + r.moe_pairs_zero <= r.moe_pairs
        assert r.moe_experts_touched <= 4 * doubles
        assert r.latent_context_sum == r.context_sum > 0
    assert sum(r.moe_pairs_held for r in decode) > 0
    zero = sum(r.moe_pairs_zero for r in decode)
    assert 0.15 < zero / sum(r.moe_pairs for r in decode) < 0.55


def test_the_new_readers_on_hand_made_contexts(monkeypatch):
    zero = _reader("moe_zero_pair_share")
    steps = [{"kind": "decode", "moe_pairs": 3072, "moe_pairs_held": 60,
              "moe_pairs_zero": 1000, "moe_experts_touched": 40},
             {"kind": "decode", "moe_pairs": 3072, "moe_pairs_held": 70,
              "moe_pairs_zero": 1048, "moe_experts_touched": 44},
             {"kind": "prefill", "real_tokens": 512}]
    assert zero.read({"steps": steps}) == pytest.approx(100 * 2048 / 6144)
    # a program without the counter (the parent commit): nothing, no error
    old = [{k: v for k, v in r.items() if k != "moe_pairs_zero"}
           for r in steps]
    assert zero.read({"steps": old}) is None
    assert zero.read({"steps": []}) is None
    # a router without such outputs reads 0, not nothing
    none = [dict(r, moe_pairs_zero=0) for r in steps[:2]]
    assert zero.read({"steps": none}) == 0.0

    lat = _reader("latent_step_dev_ms")
    assert (lat.SOURCE, lat.LAYER, lat.MOVES) == (
        "device_trace", "latent attention", "tpot_p50_ms")
    assert lat.read({"health_end": {}}) is None        # no capture
    import benchmarks.chip.layer_metrics._scopes as scopes

    summary = {"programs": {"jit_window": {
        "runs": 10, "by_scope_ms": {"attention_latent": 4.25, "mlp": 4.0}}}}
    monkeypatch.setattr(scopes, "summary", lambda ctx: summary)
    assert lat.read({}) == pytest.approx(4.25)
    # the roofline beside it multiplies one plane's walk by the latent rows
    cfgf = TABLES["longcat"]["config"]
    import json

    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           cfgf + ".json")) as f:
        file = json.load(f)
    roof = _reader("latent_attn_roofline")
    assert roof.latent_bytes(1000, 8, file) == 1000 * 8 * 576 * 2
    assert _reader("moe_expert_roofline").expert_bytes(1, file) == (
        3 * 6144 * 2048 * 2)


# ------------------------- the benchmark's data for this table --------------

CONFIG, CELL = "longcat-flash-omni-ep32", "longcat-omni-ep32.agent"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _json(*parts):
    import json

    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_every_published_number_is_the_sources():
    """Every key of the catalog entry's ``config`` stands in the file with
    the source's value, but the three in ``reduced``, which say both."""
    import json

    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    file = _json("benchmarks", "chip", "configs", CONFIG + ".json")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e.get("source_url") == file["source"])
    declared = next(c for c in _json("BENCHMARK.json")["configs"]
                    if c["name"] == CONFIG)
    assert sorted(declared["reduced"]) == sorted(file["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    assert declared["source"] == entry["source_url"]
    for key, val in entry["config"].items():
        if key in file["reduced"]:
            assert file["reduced"][key]["source"] == val, key
            assert file["reduced"][key]["here"] == file[key], key
        else:
            assert file[key] == val, key
    # the same model under the names the table and the readers read
    assert file["num_hidden_layers"] == 2 * file["num_layers"] == 8
    assert file["num_routed_experts"] == entry["config"]["n_routed_experts"]
    assert file["num_experts"] == file["n_routed_experts"] == 16
    assert file["moe_intermediate_size"] == file["expert_ffn_hidden_size"]
    assert file["mlp_layer_types"] == ["sparse", "dense"] * 4
    assert file["layer_types"] == ["mla_attention"] * 8


def test_the_file_says_what_it_assumed_and_where_it_runs():
    file = _json("benchmarks", "chip", "configs", CONFIG + ".json")
    assert {"double_layer", "router", "zero_experts", "mla_scales", "rope",
            "weights", "latent_page", "norm_topk_prob", "hidden_act",
            "tie_word_embeddings", "torch_dtype"} <= set(file["assumed"])
    assert "32 chips share each layer" in file["deployment"]
    assert file["expert_shard"] == {"index": 0, "of": 32}
    assert file["chips"] == 1 and file["reference"] == "longcat"
    assert file["engine_args"][-2:] == ["--mesh", "1,1"]
    small = file["rehearse"]["model"]
    for key in file["program_fields"].values():
        assert key in small and key in file, key


def test_the_cells_worst_case_fits_the_blocks():
    from benchmarks.chip import run as R
    from benchmarks.chip import shape as S

    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agent", 1)
    assert "32x its share" in cell["why"] and len(cell["why"]) <= 200
    names = [m["name"] for m in R.metrics_of(bench, CELL, "end_to_end")]
    assert names == ["out_tok_s", "tpot_p50_ms", "setup_s"]
    file = _json("benchmarks", "chip", "configs", CONFIG + ".json")
    eng = R.engine_dict(file["engine_args"])
    mix = S.load_mix("agent")
    assert (mix["loop"], mix["clients"], mix["prefix"]) == ("closed", 64,
                                                            None)
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 1024,
                                 "hi": 3072}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 896}
    shape = S.build_shape(mix, float(bench["run_seconds"]))
    bs = eng["block_size"]
    worst = max(-(-(r["total_len"] + r["max_tokens"]) // bs)
                for r in shape["requests"])
    assert mix["clients"] == eng["max_num_seqs"] == 64
    assert mix["clients"] * worst <= 15872 <= eng["num_blocks"] - 1
    assert max(r["total_len"] + r["max_tokens"]
               for r in shape["requests"]) <= eng["max_model_len"]
    lens = [r["total_len"] for r in shape["requests"]]
    assert 1800 < sum(lens) / len(lens) < 1920
    outs = [r["max_tokens"] for r in shape["requests"]]
    assert sum(outs) / len(outs) == pytest.approx(640, abs=8)
    assert all(r["group"] is None for r in shape["requests"])
    assert S.reachable_decode_buckets(shape, eng) == [8, 16, 32, 64]
    assert shape["summary"]["plan_digest"] == "0e60e2904109238b"
    # the two metrics this PR brings are the cell's alone
    for name in ("moe_zero_pair_share", "latent_step_dev_ms"):
        m = next(x for x in bench["per_layer"] if x["name"] == name)
        assert m["workloads"] == [CELL]
        reader = _reader(name)
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (reader.UNIT, reader.BETTER, reader.SOURCE,
                                reader.LAYER, reader.MOVES)


def test_each_limit_lies_between_its_two_readings():
    """A limit is over the largest sound reading and under the smallest
    reading of the control it refuses; the three controls of the
    mathematics and the one of the router's precision are all there."""
    file = _json("benchmarks", "chip", "limits", CONFIG + ".json")
    limits, r = file["limits"], file["readings"]
    assert set(limits) == {"both.rms_rel", "routing.short_max",
                           "router.weight_rel_max"}
    assert r["sound"]["seeds"] >= 8
    for name in ("no_identity", "no_scales", "early_join"):
        c = r["control_" + name]
        assert r["sound"]["both_rms_rel"][1] < limits["both.rms_rel"] \
            < c["both_rms_rel"][0], name
        assert r["sound"]["short_max"][1] < limits["routing.short_max"] \
            < c["short_max"][0], name
    low = r["control_router_bf16"]
    assert r["sound"]["router_weight_rel_max"][1] \
        < limits["router.weight_rel_max"] < low["router_weight_rel_max"][0]
    # and nothing else tells that control from sound
    assert low["both_rms_rel"][1] < limits["both.rms_rel"]
    assert low["short_max"][1] < limits["routing.short_max"]
