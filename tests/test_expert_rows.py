"""A sparse call whose rows follow the held pairs (PR 43):
``parallel/moe.py: held_rows`` and the slabs of ``routed_ffn``.  CPU,
float32, toy widths, Pallas interpreted; the rule and the counts at the
benchmark's real shapes (no weight of real size is made: shapes, and a
router's draw).

1. the rule by shape: the agent table's calls engage, the codegen and
   longgen tables' do not and trace the body they always had;
2. a call in slabs gives what the sum over the held experts gives, with and
   without zero-compute outputs, dead rows, a group limit and a shard that
   is not the first, and its choices and five old counters are bit for bit
   those of the call over all pairs;
3. no pair is dropped: a call built to overflow walks as many slabs as it
   takes and counts what lay behind the first;
4. the held pairs of a T=512 call and of a 64-row call at the real router
   width, against the slab;
5. the sixth counter's way to a ``StepRecord`` and the reader of
   ``moe_overflow_share``."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.observability.stepstats import StepRecord
from dynamo_tpu.parallel import moe

from test_layer_table import _eqns, _file, _model, _poisoned_gmm
from test_shortcut_table import _reader

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAT = {name: i for i, name in enumerate(moe.MOE_STATS)}
OLD = [STAT[n] for n in moe.MOE_STATS if n != "moe_pairs_overflow"]


# ------------------------- 1. the rule, by shape -----------------------------


def _call_shapes(table: str, decode: bool):
    """(tokens, top_k, held, router width, hidden, expert width) of a
    table's T=512 chunk or of its largest decode window, at the
    benchmark's real size."""
    cfg = _model(rehearse=False, table=table)
    args = _file(table)["engine_args"]
    seqs = int(args[args.index("--max-num-seqs") + 1])
    return (seqs if decode else 512, cfg.num_experts_per_token,
            cfg.experts_held[1], cfg.router_width, cfg.hidden_size,
            cfg.moe_intermediate_size)


# table, decode -> the call's rows: a slab where the rule engages
ROWS = {("longcat", False): 256, ("longcat", True): 64,
        ("ling", False): 4096, ("ling", True): 1024,
        ("laguna", False): 5120, ("laguna", True): 320}


@pytest.mark.parametrize("table,decode", sorted(ROWS))
def test_the_rule_engages_by_shape_alone(table, decode):
    """16 of 768 router outputs held: a slab of twice the even share is a
    24th and a 12th of the pairs, and the call walks slabs.  64 of 512 and
    128 of 256: a slab would be a quarter of the pairs or all of them, more
    than one part in ``HELD_SHARE``, and the rows stay all the pairs."""
    N, k, held, width, D, F = _call_shapes(table, decode)
    pairs = N * k
    tm = moe.gmm_tile(pairs, D, F)[0]
    rows = moe.held_rows(pairs, held, width, tm)
    assert rows == ROWS[table, decode] and rows % tm == 0
    if table == "longcat":
        assert moe.HELD_SHARE * rows <= pairs
        assert rows >= moe.HELD_ROOM * pairs * held / width
    else:
        assert rows == -(-pairs // tm) * tm


@pytest.mark.parametrize("table,decode", sorted(ROWS))
def test_a_call_traces_slabs_only_where_the_rule_engages(table, decode):
    """``routed_ffn`` as traced at the real shapes (nothing runs): the agent
    table's call is one loop whose grouped matmuls are a slab tall and
    hands back six counters; the two others hold no loop, no conditional,
    no array a would-be slab tall, five counters, and grouped matmuls over
    all pairs as before."""
    N, k, held, width, D, F = _call_shapes(table, decode)
    cfg = _model(rehearse=False, table=table)
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)    # noqa: E731
    kw = dict(top_k=k, held_start=cfg.experts_held[0],
              n_zero=cfg.zero_expert_num)
    if cfg.n_group:
        kw.update(score=cfg.score_function, n_group=cfg.n_group,
                  topk_group=cfg.topk_group)
    closed = jax.make_jaxpr(lambda *a: moe.routed_ffn(*a, **kw))(
        bf(N, D), bf(D, width), bf(held, D, F), bf(held, D, F),
        bf(held, F, D))
    eqns = list(_eqns(closed.jaxpr))
    rows = ROWS[table, decode]
    gmms = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(gmms) == 3
    assert {e.outvars[0].aval.shape[0] for e in gmms} == {rows}
    control = [e.primitive.name for e in eqns
               if e.primitive.name in ("while", "cond")]
    n_stats = closed.out_avals[1].shape[0]
    if table == "longcat":
        assert control == ["while"] and n_stats == len(moe.MOE_STATS)
        return
    assert not control and n_stats == len(moe.MOE_STATS) - 1
    tm = moe.gmm_tile(N * k, D, F)[0]
    slab = -(-moe.HELD_ROOM * N * k * held // width // tm) * tm
    if slab < N * k:
        assert not [v for e in eqns for v in e.outvars
                    if getattr(v.aval, "shape", ())[:1] == (slab,)]


# ------------------------- 2. a call in slabs --------------------------------

# 64 tokens x 8 choices over 128 router outputs of which 2 are held: a slab
# is one row tile of 64, an eighth of the 512 pairs
N, D, Fe, E, EH, K = 64, 24, 16, 128, 2, 8


def _draw(seed: int = 0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),  # noqa
                               jnp.float32)
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    return x, f(D, E) * 3.0, f(E, D, Fe), f(E, D, Fe), f(E, Fe, D), rng


def _call(x, wr, wg, wu, wd, lo, **kw):
    return moe.routed_ffn(x, wr, wg[lo:lo + EH], wu[lo:lo + EH],
                          wd[lo:lo + EH], top_k=K, held_start=lo,
                          interpret=True, **kw)


def _dense(x, wr, wg, wu, wd, lo, *, live=None, n_zero=0, scale=1.0,
           renormalise=True, **router):
    """The docstring's formula, expert by expert: ``sum_{e in top_k(x) and
    held} w_e swiglu_e(x)`` plus the identities' ``w_e x``, live rows
    only."""
    idx, w = moe.route(x, wr, top_k=K, renormalise=renormalise, scale=scale,
                       **router)
    idx, w = np.asarray(idx), np.asarray(w, np.float64)
    x64 = np.asarray(x, np.float64)
    out = np.zeros((N, D))
    for e in range(lo, lo + EH):
        g = x64 @ np.asarray(wg[e], np.float64)
        y = (g / (1 + np.exp(-g)) * (x64 @ np.asarray(wu[e], np.float64))
             ) @ np.asarray(wd[e], np.float64)
        out += np.where(idx == e, w, 0.0).sum(1)[:, None] * y
    out += np.where(idx >= E - n_zero, w, 0.0).sum(1)[:, None] * x64 \
        if n_zero else 0.0
    if live is not None:
        out *= np.asarray(live)[:, None]
    return out, idx


CASES = {
    "plain": (0, {}),
    "a_shard_that_is_not_the_first": (6, dict(scale=2.5)),
    "zero_compute_outputs": (4, dict(n_zero=32, renormalise=False,
                                     scale=6.0)),
    "dead_rows": (2, dict(live=True)),
    "a_group_limit": (16, dict(score="sigmoid", bias=True, n_group=8,
                               topk_group=4)),
    "all_of_them": (34, dict(score="sigmoid", bias=True, n_group=8,
                             topk_group=4, live=True, n_zero=32,
                             scale=2.5)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_call_in_slabs_is_the_sum_over_the_held_experts(case,
                                                          monkeypatch):
    lo, kw = CASES[case]
    x, wr, wg, wu, wd, rng = _draw(seed=len(case))
    kw = dict(kw)
    if kw.get("live"):
        kw["live"] = jnp.asarray(np.arange(N) % 7 != 3)
    if kw.get("bias"):
        kw["bias"] = jnp.asarray(0.05 * rng.standard_normal(E), jnp.float32)
    tm = moe.gmm_tile(N * K, D, Fe)[0]
    assert moe.held_rows(N * K, EH, E, tm) == 64 < N * K
    out, stats, chosen = _call(x, wr, wg, wu, wd, lo, **kw)
    want, idx = _dense(x, wr, wg, wu, wd, lo, **kw)
    assert int(stats[STAT["moe_pairs_held"]]) > 0
    assert int(stats[STAT["moe_pairs_overflow"]]) == 0   # one slab held it
    np.testing.assert_array_equal(np.asarray(chosen), idx)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=1e-4)
    # the same call with all its pairs as rows (the body it always had):
    # the choices and the five old counters bit for bit, the sums to
    # float32's rounding (a token's slots are added in another order)
    monkeypatch.setattr(moe, "HELD_SHARE", 10 ** 9)
    out0, stats0, chosen0 = _call(x, wr, wg, wu, wd, lo, **kw)
    assert stats0.shape == (len(moe.MOE_STATS) - 1,)
    np.testing.assert_array_equal(np.asarray(stats)[OLD], np.asarray(stats0))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out0),
                               atol=2e-6, rtol=1e-5)


def test_the_record_of_traced_calls_names_the_slab_beside_the_pairs():
    """``GMM_TILES_TRACED`` (what ``/health`` shows as ``expert_tiles`` and
    the traced log line says): a call in slabs is recorded with its pairs,
    its slab's rows and the tile ``gmm_tile`` gives for the slab."""
    x, wr, wg, wu, wd, _ = _draw(seed=9)
    jax.eval_shape(lambda: _call(x, wr, wg, wu, wd, 0))
    for k, n in ((D, Fe), (Fe, D)):
        assert moe.GMM_TILES_TRACED[N * K, 64, k, n] == moe.gmm_tile(64, k, n)


# ------------------------- 3. no pair is dropped -----------------------------


@pytest.mark.parametrize("to_held", [2, 1])
def test_a_call_that_overflows_walks_every_slab(to_held, monkeypatch):
    """Every token is sent to the held experts with ``to_held`` of its 8
    choices: 128 or 64 + held pairs in a call shaped for 64.  The result is
    still the sum over the held experts, every token keeps all its experts,
    and ``moe_pairs_overflow`` counts what lay behind the first slab; rows
    of no group (poisoned) reach nothing."""
    lo = 6
    x, wr, wg, wu, wd, _ = _draw(seed=3)
    bias = jnp.zeros((E,)).at[lo:lo + to_held].set(10.0)
    if to_held == 1:          # the second held expert gets its usual share
        bias = bias.at[lo + 1].set(0.002)
    _poisoned_gmm(monkeypatch)
    out, stats, chosen = _call(x, wr, wg, wu, wd, lo, bias=bias, scale=2.5)
    want, idx = _dense(x, wr, wg, wu, wd, lo, bias=bias, scale=2.5)
    held = int(((idx >= lo) & (idx < lo + EH)).sum())
    assert held >= to_held * N and held > 64
    assert int(stats[STAT["moe_pairs_held"]]) == held
    assert int(stats[STAT["moe_pairs_overflow"]]) == held - 64
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(chosen), idx)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-5, rtol=1e-4)


def test_a_call_with_no_held_pair_walks_no_slab():
    """All choices pushed off the held experts: no pass, zeros, no NaN."""
    x, wr, wg, wu, wd, _ = _draw(seed=5)
    bias = jnp.zeros((E,)).at[6:8].set(-10.0)
    out, stats, _ = _call(x, wr, wg, wu, wd, 6, bias=bias)
    assert [int(stats[STAT[n]]) for n in (
        "moe_pairs_held", "moe_experts_touched", "moe_pairs_overflow")] \
        == [0, 0, 0]
    assert np.all(np.asarray(out) == 0)


# ------------------------- 4. counts at the real router width ---------------


@pytest.mark.parametrize("seed", [4300000011, 4300000012, 4300000013,
                                  4300000014, 4300000015])
def test_a_slab_holds_the_held_pairs_of_the_real_router(seed):
    """The agent configuration's router as the program draws it (``[6144,
    768]`` bfloat16 normal / sqrt(6144), a choice bias N(0, (0.25/768)^2))
    on seeded unit-variance tokens: a T=512 call holds 128 pairs of its 6144
    on average with a deviation of ~11, a 64-row call 16 +- 4; their slabs
    of 256 and 64 rows stand 6 deviations and more above what any of these
    draws shows.  (Prefill records carry no routing counters: this count,
    and the same one on the chip at the model's own hidden states in
    PERF.md, is what bounds a chunk's overflow; decode's is measured:
    ``moe_overflow_share``.)"""
    cfg = _model(rehearse=False, table="longcat")
    D_, W, k = cfg.hidden_size, cfg.router_width, cfg.num_experts_per_token
    lo, held = cfg.experts_held
    assert (D_, W, k, lo, held) == (6144, 768, 12, 0, 16)
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (512, D_), jnp.float32).astype(jnp.bfloat16)
    wr = (jax.random.normal(kw, (D_, W), jnp.float32)
          / np.sqrt(D_)).astype(jnp.bfloat16)
    bias = 0.25 / W * jax.random.normal(kb, (W,), jnp.float32)
    idx, _ = moe.route(x, wr, top_k=k, renormalise=False, scale=6.0,
                       bias=bias)
    here = (np.asarray(idx) >= lo) & (np.asarray(idx) < lo + held)
    chunk, step = int(here.sum()), [int(here[i:i + 64].sum())
                                    for i in range(0, 512, 64)]
    F_ = cfg.moe_intermediate_size
    slab_chunk = moe.held_rows(512 * k, held, W, moe.gmm_tile(
        512 * k, D_, F_)[0])
    slab_step = moe.held_rows(64 * k, held, W, moe.gmm_tile(
        64 * k, D_, F_)[0])
    assert (slab_chunk, slab_step) == (256, 64)
    # even draw: mean 128, deviation sqrt(6144 * p * (1 - p)) = 11.2
    assert 128 - 5 * 11.2 < chunk < 128 + 5 * 11.2 < slab_chunk - 6 * 11.2
    assert max(step) <= 16 + 6 * 4 < slab_step


# ------------------------- 5. the counter and its reader --------------------


def test_the_sixth_counter_rides_the_row_behind_the_load():
    """``moe_stats_row`` takes rows of six (calls in slabs) as rows of five
    (calls over all pairs: the row the window always had, a zero where the
    sixth would be); the engine reads either back by ``MOE_STATS``."""
    assert moe.MOE_STATS.index("moe_load_max") == 4
    assert moe.MOE_STATS[-1] == "moe_pairs_overflow"
    assert "moe_pairs_overflow" in {
        f.name for f in dataclasses.fields(StepRecord)}
    six = [jnp.asarray([96, 10, 7, 30, 3, 0], jnp.int32),
           jnp.asarray([96, 70, 9, 34, 5, 6], jnp.int32)]
    five = [s[:5] for s in six]
    for stats, want in ((six, [192, 80, 16, 64, 5, 6, 0, 0]),
                        (five, [192, 80, 16, 64, 5, 0, 0, 0])):
        row = np.asarray(M.moe_stats_row(stats, 8))
        assert row.tolist() == [want]
        rec = StepRecord(kind="decode", t_dispatch=0.0)
        for name, v in zip(moe.MOE_STATS, row[0]):
            setattr(rec, name, int(v))
        assert (rec.moe_load_max, rec.moe_pairs_overflow) == (5, want[5])
    with pytest.raises(ValueError, match="routing counters"):
        M.moe_stats_row(six, 4)


def test_the_overflow_reader_on_hand_made_contexts():
    over = _reader("moe_overflow_share")
    steps = [{"kind": "decode", "moe_pairs": 3072, "moe_pairs_held": 60,
              "moe_pairs_zero": 1000, "moe_pairs_overflow": 0},
             {"kind": "decode", "moe_pairs": 3072, "moe_pairs_held": 140,
              "moe_pairs_zero": 1048, "moe_pairs_overflow": 5},
             {"kind": "prefill", "real_tokens": 512}]
    assert over.read({"steps": steps}) == pytest.approx(100 * 5 / 200)
    # a program without the counter (the parent commit): nothing, no error
    old = [{k: v for k, v in r.items() if k != "moe_pairs_overflow"}
           for r in steps]
    assert over.read({"steps": old}) is None
    assert over.read({"steps": []}) is None
    # a program whose rule never engages reads 0, not nothing
    never = [dict(r, moe_pairs_overflow=0) for r in steps[:2]]
    assert over.read({"steps": never}) == 0.0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "moe_overflow_share"]
    assert entry == [{
        "name": "moe_overflow_share", "unit": over.UNIT,
        "better": over.BETTER, "source": over.SOURCE, "layer": over.LAYER,
        "moves": over.MOVES, "workloads": ["longcat-omni-ep32.agent"]}]
    assert (over.UNIT, over.BETTER, over.SOURCE, over.LAYER, over.MOVES) == (
        "%", "lower", "program_counter", "expert layer", "tpot_p50_ms")
