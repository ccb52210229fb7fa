"""Engine flight recorder suite (PR 9, `-m observability`).

Covers the shared FLOPs model (parameter-count parity against the real
``init_params`` tree), stepstats windowed invariants, the compile-and-remat
watchdog (including the acceptance criterion: steady-state recompiles stay
flat after warmup while a seeded shape change is detected AND attributed
to its jitted function), the /debug/profile endpoint, Prometheus text
exposition conformance, aggregator forward-compat + stale expiry for the
new per-worker gauges, and the offline report CLI golden.
"""

import dataclasses
import json
import logging
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as model_lib
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request
from dynamo_tpu.observability import compilewatch
from dynamo_tpu.observability import flops as flops_lib
from dynamo_tpu.observability.flops import (
    FlopsModel, active_param_count, param_count, peak_flops,
)
from dynamo_tpu.observability.gauges import EngineObsGauges
from dynamo_tpu.observability.report import load_records, render_report
from dynamo_tpu.observability.stepstats import (
    DECODE, PREFILL, SPEC_VERIFY, StepRecord, StepStats,
)
from dynamo_tpu.utils.metrics import MetricsRegistry, validate_exposition

pytestmark = pytest.mark.observability


# ---------------------------------------------------------------------------
# FLOPs model
# ---------------------------------------------------------------------------

def _real_param_count(cfg: ModelConfig) -> int:
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("cfg_name", ["tiny", "tiny_moe", "tiny_tied"])
def test_param_count_matches_init_params(cfg_name):
    """The analytic count is EXACT against the real parameter tree —
    dense, MoE, and tied-embedding variants."""
    if cfg_name == "tiny":
        cfg = ModelConfig.tiny()
    elif cfg_name == "tiny_moe":
        cfg = ModelConfig.tiny_moe()
    else:
        cfg = dataclasses.replace(ModelConfig.tiny(),
                                  tie_word_embeddings=True)
    assert param_count(cfg) == _real_param_count(cfg)


def test_active_param_count_excludes_gather_includes_lm_head():
    cfg = ModelConfig.tiny()
    # untied: active = total - embedding table (lm_head already counted)
    assert (active_param_count(cfg)
            == param_count(cfg) - cfg.vocab_size * cfg.hidden_size)
    tied = dataclasses.replace(cfg, tie_word_embeddings=True)
    # tied: the one table is both gather (excluded) and lm_head (included),
    # so active matches the untied case exactly
    assert active_param_count(tied) == active_param_count(cfg)


def test_flops_model_attention_term():
    """step_flops = 2·active·tokens + 4·L·H·hd·context_sum — the attention
    term the old 2·N·tokens bench formula dropped."""
    cfg = ModelConfig.tiny()
    fm = FlopsModel(cfg)
    attn_coef = 4.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim_
    assert fm.attn_coef == attn_coef
    assert fm.step_flops(10, 0) == pytest.approx(
        2.0 * active_param_count(cfg) * 10)
    assert fm.step_flops(0, 100) == pytest.approx(attn_coef * 100)
    assert fm.step_flops(10, 100) == pytest.approx(
        fm.step_flops(10, 0) + fm.step_flops(0, 100))
    # causal prefill context sum: positions start..start+len-1 attend pos+1
    assert fm.sequence_context_sum(4, start=0) == 1 + 2 + 3 + 4
    assert fm.sequence_context_sum(3, start=10) == 11 + 12 + 13
    assert fm.sequence_context_sum(0) == 0
    # longer context must cost strictly more than the matmul-only estimate
    assert fm.sequence_flops(128, 32) > fm.matmul_per_token * 160


def test_peak_flops_table():
    assert peak_flops("TPU v5e", "tpu") == 197e12
    assert peak_flops("TPU v5p", "tpu") == 459e12
    # v5p must not be swallowed by the shorter "v5" key
    assert peak_flops("TPU v6e", "tpu") == 918e12
    # fp32 halves the MXU rate
    assert peak_flops("TPU v5e", "tpu", "float32") == 197e12 / 2
    # unknown TPU kind -> an error, never a default; non-TPU -> no peak
    with pytest.raises(ValueError, match="v9x"):
        peak_flops("TPU v9x", "tpu")
    assert peak_flops("", "cpu") is None


# ---------------------------------------------------------------------------
# StepStats
# ---------------------------------------------------------------------------

def _mk_stats(tmp_path=None, **kw):
    clock = {"t": 100.0}
    kw.setdefault("n_chips", 1)
    kw.setdefault("peak_flops", 1e9)
    kw.setdefault("window_s", 10.0)
    stats = StepStats(FlopsModel(ModelConfig.tiny()),
                      clock=lambda: clock["t"], **kw)
    return stats, clock


def test_stepstats_window_invariants():
    stats, clock = _mk_stats()
    rec = StepRecord(kind=PREFILL, t_dispatch=100.0, t_land=100.1,
                     rows=1, live_rows=1, padded_tokens=32, real_tokens=20,
                     goodput_tokens=20, context_sum=210)
    stats.commit(rec)
    # commit fills the FLOPs fields from the shared model
    fm = stats.flops_model
    assert rec.flops_real == pytest.approx(fm.step_flops(20, 210))
    assert rec.flops_dispatched == pytest.approx(
        fm.step_flops(32, 210 * 32 / 20))
    assert rec.flops_goodput == rec.flops_real  # goodput == real tokens
    clock["t"] = 101.0
    snap = stats.snapshot(max_age_s=0.0)
    assert snap["steps_in_window"] == 1
    assert snap["goodput_tok_s"] == pytest.approx(20.0)  # 20 tok / 1 s
    assert 0.0 < snap["padding_waste_ratio"] < 1.0
    assert snap["padding_waste_ratio"] == pytest.approx(
        (rec.flops_dispatched - rec.flops_real) / rec.flops_dispatched)
    assert snap["spec_reject_waste_ratio"] == 0.0
    # all-goodput prefill: mfu == mfu_prefill, decode share is zero
    assert snap["mfu"] == pytest.approx(snap["mfu_prefill"])
    assert snap["mfu_decode"] == 0.0
    assert snap["mfu"] == pytest.approx(
        rec.flops_goodput / (1.0 * stats.peak_flops))
    assert snap["mfu_dispatched"] > snap["mfu"]


def test_stepstats_spec_waste_split():
    stats, clock = _mk_stats()
    # spec verify window: 25 real tokens computed, only 15 advanced seqs
    stats.commit(StepRecord(kind=SPEC_VERIFY, t_dispatch=100.0, t_land=100.2,
                            rows=8, live_rows=5, padded_tokens=40,
                            real_tokens=25, goodput_tokens=15,
                            context_sum=500, spec_drafted=20,
                            spec_accepted=10))
    clock["t"] = 100.5
    snap = stats.snapshot(max_age_s=0.0)
    assert snap["spec_reject_waste_ratio"] > 0.0
    assert snap["padding_waste_ratio"] > 0.0
    # waste ratios + goodput fraction partition dispatched FLOPs
    goodput_frac = snap["mfu"] / snap["mfu_dispatched"]
    assert (snap["padding_waste_ratio"] + snap["spec_reject_waste_ratio"]
            + goodput_frac) == pytest.approx(1.0)
    assert snap["spec_drafted"] == 20 and snap["spec_accepted"] == 10


def test_stepstats_window_pruning_and_warmup_reset():
    stats, clock = _mk_stats(window_s=10.0)
    stats.commit(StepRecord(kind=DECODE, t_dispatch=100.0, t_land=100.1,
                            padded_tokens=8, real_tokens=4,
                            goodput_tokens=4, context_sum=40))
    clock["t"] = 105.0
    assert stats.snapshot(max_age_s=0.0)["steps_in_window"] == 1
    clock["t"] = 120.0  # landing now older than window_s
    snap = stats.snapshot(max_age_s=0.0)
    assert snap["steps_in_window"] == 0
    assert snap["goodput_tok_s"] == 0.0
    # lifetime totals survive the window rollover...
    assert snap["total_steps"] == 1
    # ...but not the warmup reset
    stats.mark_warmup_done()
    snap = stats.snapshot(max_age_s=0.0)
    assert snap["total_steps"] == 0 and snap["total_goodput_tokens"] == 0


def test_stepstats_snapshot_cache():
    stats, clock = _mk_stats()
    a = stats.snapshot(max_age_s=10.0)
    stats.commit(StepRecord(kind=DECODE, t_dispatch=100.0, t_land=100.0,
                            padded_tokens=8, real_tokens=8,
                            goodput_tokens=8, context_sum=8))
    # a commit invalidates the cache even inside max_age_s
    b = stats.snapshot(max_age_s=10.0)
    assert a["steps_in_window"] == 0 and b["steps_in_window"] == 1


def test_stepstats_jsonl_capture(tmp_path):
    path = tmp_path / "steps.jsonl"
    stats, clock = _mk_stats(jsonl_path=str(path))
    stats.commit(StepRecord(kind=PREFILL, t_dispatch=100.0, t_land=100.1,
                            padded_tokens=16, real_tokens=5,
                            goodput_tokens=5, context_sum=15))
    stats.commit(StepRecord(kind=DECODE, t_dispatch=100.1, t_land=100.2,
                            padded_tokens=8, real_tokens=2,
                            goodput_tokens=2, context_sum=12))
    stats.close()
    with open(path) as fh:
        records = load_records(fh)
    assert [r["kind"] for r in records] == [PREFILL, DECODE]
    # FLOPs fields were filled before serialization
    assert all(r["flops_dispatched"] > 0 for r in records)


# ---------------------------------------------------------------------------
# Compile watchdog
# ---------------------------------------------------------------------------

@pytest.fixture
def watch():
    compilewatch.install()
    w = compilewatch.get_watch()
    w.reset()
    yield w
    w.reset()


def test_compilewatch_attribution_and_steady_state(watch):
    # build inputs up front: array creation itself compiles incidental
    # fill helpers, which belong in warmup (the <unattributed> bucket)
    a4, z4, b8 = (jnp.ones((4,), jnp.float32), jnp.zeros((4,), jnp.float32),
                  jnp.ones((8,), jnp.float32))
    fn = compilewatch.label(jax.jit(lambda x: x * 2 + 1), "obs_test_dbl")
    fn(a4).block_until_ready()
    assert watch.snapshot()["compiles_by_fn"].get("obs_test_dbl") == 1
    assert watch.compile_secs["obs_test_dbl"] > 0.0
    # cache hit: same shape compiles nothing
    fn(z4).block_until_ready()
    assert watch.snapshot()["compiles_by_fn"]["obs_test_dbl"] == 1
    watch.mark_warmup_done()
    fn(a4).block_until_ready()
    assert watch.steady_total() == 0
    # the seeded shape change is detected AND attributed to its function
    fn(b8).block_until_ready()
    assert watch.steady_by_label() == {"obs_test_dbl": 1}
    snap = watch.snapshot()
    assert snap["recompiles_steady_state"] == 1
    assert snap["recompiles_by_fn"] == {"obs_test_dbl": 1}


def test_compilewatch_label_preserves_callable(watch):
    jitted = jax.jit(lambda x: x + 1)
    wrapped = compilewatch.label(jitted, "obs_test_add")
    assert wrapped.__wrapped__ is jitted
    assert wrapped.__compile_label__ == "obs_test_add"
    out = wrapped(jnp.asarray([1, 2], jnp.int32))
    assert out.tolist() == [2, 3]


def test_assert_no_recompiles_helper(watch):
    fn = compilewatch.label(jax.jit(lambda x: x - 3), "obs_test_sub")
    fn(jnp.ones((4,), jnp.float32)).block_until_ready()
    with compilewatch.assert_no_recompiles():
        fn(jnp.zeros((4,), jnp.float32)).block_until_ready()
    with pytest.raises(AssertionError, match="obs_test_sub"):
        with compilewatch.assert_no_recompiles():
            fn(jnp.ones((16,), jnp.float32)).block_until_ready()


def test_remat_warning_parsing(watch):
    text = ("W0000 [SPMD] Involuntary full rematerialization of f32[2048]\n"
            "noise\n"
            "w1234 [spmd] involuntary full rematerialization again\n")
    assert compilewatch.scan_log_text(text) == 2
    assert watch.snapshot()["involuntary_remats_total"] == 2
    # warnings that reach Python logging (jax/absl bridges) count too
    logging.getLogger("jax").warning(
        "[SPMD] Involuntary full rematerialization of %s", "f32[8,128]")
    assert watch.snapshot()["involuntary_remats_total"] == 3
    # steady-state counter only ticks after the warmup mark
    assert watch.snapshot()["involuntary_remats_steady"] == 0
    watch.mark_warmup_done()
    compilewatch.scan_log_text("[SPMD] Involuntary full rematerialization")
    snap = watch.snapshot()
    assert snap["involuntary_remats_total"] == 4
    assert snap["involuntary_remats_steady"] == 1


# ---------------------------------------------------------------------------
# Engine integration: the acceptance criterion
# ---------------------------------------------------------------------------

async def _run(engine, prompt, n=4):
    req = Request(request_id=f"obs-{prompt[0]}-{len(prompt)}-{n}",
                  token_ids=prompt, max_tokens=n, temperature=0.0,
                  ignore_eos=True)
    return [out.token_id async for out in engine.submit(req)]


@pytest.mark.anyio
async def test_engine_steady_state_recompiles_flat_then_seeded_shape(watch):
    """ISSUE 9 acceptance: after warmup, engine_recompiles_total stays flat
    over same-shape traffic; a seeded shape change (a prompt spilling into
    the next prefill bucket) is detected and attributed to its jitted fn."""
    engine = InferenceEngine(
        ModelConfig.tiny(),
        EngineConfig(
            block_size=4, num_blocks=64, max_num_seqs=4,
            max_num_batched_tokens=64, max_model_len=128,
            decode_buckets=(8,), prefill_buckets=(16, 32),
        ),
    )
    assert engine.obs is not None  # recorder on by default
    await engine.start()
    try:
        # warmup: two requests in the T=16 prefill bucket
        assert len(await _run(engine, [5, 6, 7, 8, 9])) == 4
        assert len(await _run(engine, [9, 8, 7])) == 4
        assert watch.snapshot()["compiles_total"] > 0
        engine.mark_obs_warmup_done()

        # steady state: identical shapes — the recorder must stay flat
        assert len(await _run(engine, [1, 2, 3, 4, 5])) == 4
        snap = engine.obs_snapshot()
        assert snap["recompiles_steady_state"] == 0
        assert snap["recompiles_by_fn"] == {}
        assert snap["total_steps"] > 0
        assert snap["goodput_tok_s"] > 0.0
        # CPU has no published peak: MFU is absent, not nominal
        assert "mfu" not in snap and "mfu_prefill" not in snap
        assert 0.0 <= snap["padding_waste_ratio"] < 1.0

        # seeded shape change: a prompt that needs the T=32 bucket
        assert len(await _run(engine, list(range(2, 22)))) == 4
        steady = watch.steady_by_label()
        assert any(fn.startswith("packed_prefill_T32") for fn in steady), (
            f"seeded recompile not attributed: {steady!r}")
        assert engine.obs_snapshot()["recompiles_steady_state"] >= 1
    finally:
        await engine.stop()


@pytest.mark.anyio
async def test_engine_obs_spans_and_gauges(watch):
    """EngineObsGauges mints the engine_* series and returns a scalar-only
    wire dict for the load-metrics publisher."""
    engine = InferenceEngine(
        ModelConfig.tiny(),
        EngineConfig(block_size=4, num_blocks=64, max_num_seqs=4,
                     max_num_batched_tokens=64, max_model_len=128,
                     decode_buckets=(8,), prefill_buckets=(16,)),
    )
    await engine.start()
    try:
        await _run(engine, [3, 1, 4, 1, 5])
        registry = MetricsRegistry()
        gauges = EngineObsGauges(registry, engine)
        wire = gauges.refresh()
        assert wire["goodput_tok_s"] > 0.0
        assert wire["recompiles_steady_state"] == 0
        # non-scalar snapshot entries (per-fn dicts) stay off the wire
        assert all(isinstance(v, (int, float)) for v in wire.values())
        body = registry.render()
        names = {s.name for s in validate_exposition(body)}
        # no MFU gauge at all on a platform without a published peak
        assert not any(n.startswith("dynamo_engine_mfu") for n in names)
        for expect in ("dynamo_engine_goodput_tok_s",
                       "dynamo_engine_padding_waste_ratio",
                       "dynamo_engine_wasted_flops_ratio",
                       "dynamo_engine_involuntary_remats_total"):
            assert expect in names, f"{expect} missing from exposition"
    finally:
        await engine.stop()


# ---------------------------------------------------------------------------
# /debug/profile + /metrics conformance over HTTP
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_profile_endpoint_and_metrics_content_type(tmp_path):
    import os

    import aiohttp
    from prometheus_client import CONTENT_TYPE_LATEST

    from dynamo_tpu.runtime.system_server import SystemServer

    metrics = MetricsRegistry()
    metrics.gauge("obs_demo_gauge", "demo").set(1.5)
    server = SystemServer(metrics=metrics, host="127.0.0.1", port=0)
    await server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        async with aiohttp.ClientSession() as sess:
            async with sess.get(
                    f"{base}/debug/profile",
                    params={"ms": "50", "dir": str(tmp_path)}) as resp:
                assert resp.status == 200
                data = await resp.json()
            assert os.path.isdir(data["trace_dir"])
            assert data["trace_dir"].startswith(str(tmp_path))
            assert data["requested_ms"] == 50
            assert data["captured_ms"] >= 50
            async with sess.get(f"{base}/debug/profile",
                                params={"ms": "oops"}) as resp:
                assert resp.status == 400
            async with sess.get(f"{base}/metrics") as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"] == CONTENT_TYPE_LATEST
                body = await resp.read()
        samples = validate_exposition(body)
        assert any(s.name == "dynamo_obs_demo_gauge" and s.value == 1.5
                   for s in samples)
    finally:
        await server.stop()


@pytest.mark.anyio
async def test_profile_busy_returns_409():
    import asyncio

    from dynamo_tpu.observability import profiling

    # hold the capture lock as a concurrent capture would
    assert profiling._capture_lock.acquire(blocking=False)
    try:
        with pytest.raises(profiling.ProfileBusyError):
            await profiling.capture(10)
    finally:
        profiling._capture_lock.release()
    _ = asyncio


def test_prometheus_exposition_nasty_label_values():
    """Label values with newlines, quotes, and backslashes must round-trip
    the reference parser unchanged — the escaping satellite."""
    registry = MetricsRegistry()
    g = registry.gauge("obs_nasty_gauge", 'help with "quotes" and \\slash',
                       ["fn"])
    nasty = ['line\nbreak', 'quo"te', 'back\\slash', 'plain']
    for i, val in enumerate(nasty):
        g.labels(fn=val).set(float(i))
    samples = validate_exposition(registry.render())
    seen = {s.labels["fn"]: s.value for s in samples
            if s.name == "dynamo_obs_nasty_gauge"}
    assert seen == {val: float(i) for i, val in enumerate(nasty)}


# ---------------------------------------------------------------------------
# Aggregator forward-compat + stale expiry
# ---------------------------------------------------------------------------

def _agg(clock):
    from dynamo_tpu.metrics_aggregator import MetricsAggregator

    metrics = MetricsRegistry()
    runtime = SimpleNamespace(
        metrics=metrics,
        namespace=lambda *a, **k: SimpleNamespace(
            component=lambda name: SimpleNamespace(
                event_subject=lambda s: f"x.{name}.{s}")),
    )
    return MetricsAggregator(runtime, "backend", stale_after_s=30.0,
                             clock=lambda: clock["t"]), metrics


def test_aggregator_obs_forward_compat_and_expiry():
    clock = {"t": 1000.0}
    agg, metrics = _agg(clock)
    # new-style worker publishes "obs"; old-style worker omits it entirely
    agg._on_stats({"worker_id": "w-new", "kv_usage": 0.5,
                   "obs": {"mfu": 0.4, "goodput_tok_s": 120.0,
                           "padding_waste_ratio": 0.25,
                           "spec_reject_waste_ratio": 0.05}})
    agg._on_stats({"worker_id": "w-old", "kv_usage": 0.1})
    samples = validate_exposition(metrics.render())
    by_series = {(s.name, s.labels.get("worker")): s.value for s in samples}
    assert by_series[("dynamo_worker_mfu", "w-new")] == 0.4
    assert by_series[("dynamo_worker_goodput_tok_s", "w-new")] == 120.0
    assert by_series[("dynamo_worker_padding_waste_ratio", "w-new")] == 0.25
    # forward-compat: the obs-less worker reads zero, not KeyError
    assert by_series[("dynamo_worker_mfu", "w-old")] == 0.0
    # planner-signal aggregates: mean over publishers, goodput summed;
    # the obs-less worker does NOT drag the mean down
    assert agg._obs_mean("mfu") == pytest.approx(0.4)
    assert agg.goodput_tok_s() == pytest.approx(120.0)

    # stale expiry clears the new per-worker label sets too
    clock["t"] = 1031.0
    agg._on_stats({"worker_id": "w-new", "kv_usage": 0.5,
                   "obs": {"mfu": 0.4, "goodput_tok_s": 120.0,
                           "padding_waste_ratio": 0.25}})
    samples = validate_exposition(metrics.render())
    workers = {s.labels.get("worker") for s in samples
               if s.name in ("dynamo_worker_mfu",
                             "dynamo_worker_goodput_tok_s",
                             "dynamo_worker_padding_waste_ratio")}
    assert workers == {"w-new"}, f"stale worker gauges leaked: {workers}"


def test_aggregator_obs_mean_none_without_recorders():
    clock = {"t": 1000.0}
    agg, _ = _agg(clock)
    agg._on_stats({"worker_id": "w-old", "kv_usage": 0.1})
    # signals must distinguish "no recorder" (None) from "recorder says 0"
    assert agg._obs_mean("mfu") is None
    assert agg.goodput_tok_s() is None


# ---------------------------------------------------------------------------
# Config knobs
# ---------------------------------------------------------------------------

def test_runtime_config_obs_env_knobs(monkeypatch, tmp_path):
    from dynamo_tpu.utils.config import RuntimeConfig

    cfg = RuntimeConfig()
    assert cfg.obs_enabled is True
    assert cfg.obs_window_s == 10.0
    assert cfg.obs_stepstats_path == "" and cfg.obs_profile_dir == ""
    monkeypatch.setenv("DYNTPU_OBS_ENABLED", "0")
    monkeypatch.setenv("DYNTPU_OBS_WINDOW_S", "5.5")
    monkeypatch.setenv("DYNTPU_OBS_STEPSTATS_PATH",
                       str(tmp_path / "steps.jsonl"))
    monkeypatch.setenv("DYNTPU_OBS_PROFILE_DIR", str(tmp_path / "traces"))
    cfg = RuntimeConfig.from_settings()
    assert cfg.obs_enabled is False
    assert cfg.obs_window_s == 5.5
    assert cfg.obs_stepstats_path == str(tmp_path / "steps.jsonl")
    assert cfg.obs_profile_dir == str(tmp_path / "traces")


# ---------------------------------------------------------------------------
# Offline report CLI
# ---------------------------------------------------------------------------

_REPORT_RECORDS = [
    {"kind": "prefill", "t_dispatch": 0.0, "t_land": 0.2,
     "padded_tokens": 32, "real_tokens": 20, "goodput_tokens": 20,
     "flops_dispatched": 3200.0, "flops_real": 2000.0,
     "flops_goodput": 2000.0},
    {"kind": "decode", "t_dispatch": 0.2, "t_land": 0.5,
     "padded_tokens": 16, "real_tokens": 8, "goodput_tokens": 8,
     "flops_dispatched": 1600.0, "flops_real": 800.0,
     "flops_goodput": 800.0},
    {"kind": "spec_verify", "t_dispatch": 0.5, "t_land": 1.0,
     "padded_tokens": 40, "real_tokens": 25, "goodput_tokens": 15,
     "spec_drafted": 20, "spec_accepted": 10,
     "flops_dispatched": 4000.0, "flops_real": 2500.0,
     "flops_goodput": 1500.0},
]

_REPORT_GOLDEN = """\
engine flight recorder — where did the time go
==============================================================
records: 3   wall: 1.000s   goodput: 43 tok (43.0 tok/s)

class         steps      tok  pad tok   busy s  share  waste
--------------------------------------------------------------
decode            1        8        8    0.300  18.2%  50.0%
prefill           1       20       12    0.200  36.4%  37.5%
spec_verify       1       15       15    0.500  45.5%  62.5%
--------------------------------------------------------------
padding waste:      39.8% of dispatched FLOPs
spec-reject waste:  11.4% of dispatched FLOPs
goodput FLOPs:      48.9% of dispatched
spec acceptance:   10/20 (50.0%)
"""


def test_report_golden():
    assert render_report(list(_REPORT_RECORDS)) == _REPORT_GOLDEN
    assert render_report([]) == "no step records\n"


def test_report_prints_host_ms_per_step():
    """Records that carry the host seconds add one line: mean per batch of
    the loop's, the dispatch thread's and the fetch thread's time."""
    recs = [dict(r) for r in _REPORT_RECORDS]
    assert "host per step" not in render_report(recs)
    # two batches: (prefill + decode) and the spec window
    recs[0].update(dispatch_s=0.001)
    recs[1].update(host_s=0.004, dispatch_s=0.002, unpack_s=0.0005)
    recs[2].update(host_s=0.002, dispatch_s=0.003, unpack_s=0.0015)
    wall = (max(r["t_land"] for r in recs)
            - min(r["t_dispatch"] for r in recs))
    line = render_report(recs).splitlines()[-1]
    assert line == (
        "host per step:     loop 3.00 ms  dispatch 3.00 ms  unpack 1.00 ms"
        f"  (loop busy {100 * 0.006 / wall:.1f}% of wall)")
    # the whole event loop's busy seconds, where the records carry them,
    # and the part of them the loop thread spent on the CPU
    recs[1].update(loop_busy_s=0.010, loop_cpu_s=0.006)
    recs[2].update(loop_busy_s=0.005, loop_cpu_s=0.003)
    assert render_report(recs).splitlines()[-1] == line[:-1] + (
        f"; the whole event loop busy {100 * 0.015 / wall:.1f}%,"
        f" on the CPU {100 * 0.009 / wall:.1f}%)")


def test_selfcheck_scopes_and_vocabulary():
    """The benchmark's by-scope reducer passes its own checks, and the
    vocabulary it spells out (it must run without JAX) is the model's."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip",
        "selfcheck_scopes.py")
    spec = importlib.util.spec_from_file_location("selfcheck_scopes", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # (the stages of a table's layers came behind it in PR 32; the file is
    # the benchmark's and spells out the stages its recorded trace has)
    assert mod.VOCABULARY + model_lib.TABLE_SCOPES == model_lib.SCOPES
    mod.check_hand_made()
    assert mod.check_recorded()


def test_report_cli_main(tmp_path, capsys):
    from dynamo_tpu.observability.report import main

    path = tmp_path / "steps.jsonl"
    path.write_text(
        "".join(json.dumps(r) + "\n" for r in _REPORT_RECORDS))
    assert main([str(path)]) == 0
    assert capsys.readouterr().out == _REPORT_GOLDEN


# ---------------------------------------------------------------------------
# Names on both clocks (PR 24): scopes in the step programs, engine-loop
# phases in a capture, host seconds on the step records
# ---------------------------------------------------------------------------

def _tiny_engine_config(**kw):
    return EngineConfig(block_size=4, num_blocks=64, max_num_seqs=4,
                        max_num_batched_tokens=64, max_model_len=128,
                        decode_buckets=(8,), prefill_buckets=(16,), **kw)


# the benchmark's tables of layer kinds (their rehearsal models), and the
# stages only each of them has: K / V kinds with a window, or linear and
# latent kinds, whose recurrence is a decode program's and whose chunked
# form a prefill program's
_TABLES = {
    "table": ("laguna-s-2.1-ep2", {
        "attention_window", "attn_gate", "moe_router", "moe_experts",
        "moe_shared"}),
    "hybrid": ("ling-3.0-flash-ep8", {
        "attn_gate", "moe_router", "moe_experts", "moe_shared", "kda_proj",
        "kda_conv", "kda_out", "attention_latent", "kda_recurrent",
        "kda_chunk"}),
    # double layers of latent rows: the first dense FFN runs as the sparse
    # row's shared expert under "mlp", so no "moe_shared" and no gate
    "shortcut": ("longcat-flash-omni-ep32", {
        "moe_router", "moe_experts", "moe_zero", "moe_shortcut",
        "attention_latent"}),
    # gated-delta-rule layers beside full attention over K / V pages, dense
    # FFNs: the output norms are in "o_proj" / "mlp", the QK-norm in
    # "qkv_proj"
    "gated_delta": ("olmo-hybrid-7b-l8", {
        "gdn_proj", "gdn_conv", "gdn_out", "gdn_recurrent", "gdn_chunk"}),
}


def _table_model(name="laguna-s-2.1-ep2"):
    """A tiny table of layer kinds of the benchmark (a rehearsal model)."""
    import os

    from benchmarks.chip import worker_launch

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip", "configs",
        f"{name}.json")
    with open(path) as f:
        return worker_launch.model_config_from(json.load(f), True)


def _lowered_step_program(which, cfg=None):
    cfg, eng = cfg or ModelConfig.tiny(), _tiny_engine_config()
    params = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: model_lib.init_cache(cfg, eng))
    S, wcap = eng.max_num_seqs, 32
    if which == "decode_window":
        ctl = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
            model_lib.init_ctl(eng, S, wcap))
        fn, _ = model_lib.make_autopilot_fns(cfg, eng, wcap)
        args = (params, cache, ctl, jax.ShapeDtypeStruct((8,), jnp.int32))
    else:
        T, W = 16, 8
        fn = model_lib.make_packed_prefill_fn(cfg, eng, T, W)
        args = (params, cache,
                jax.ShapeDtypeStruct((S + 1,), jnp.int32),
                jax.ShapeDtypeStruct((1, T + W + model_lib.PP_SCALARS),
                                     jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
    # compilewatch.label wraps the jitted callable
    return fn.__wrapped__.lower(*args)


@pytest.mark.parametrize("model", ["one_kind", "table", "hybrid", "shortcut",
                                   "gated_delta"])
@pytest.mark.parametrize("which", ["decode_window", "packed_prefill"])
def test_step_programs_carry_every_scope(which, model):
    """Every name of model.SCOPES is a component of some op's ``op_name``
    in the lowered program — a refactor that drops a stage's
    ``jax.named_scope`` fails here, before a trace reads 0 for it.  A table
    of layer kinds carries the stages of its kinds (between them the tables
    carry every one of ``TABLE_SCOPES``) and none of another table's; a
    one-kind model all but the tables' own, and none of those."""
    import re

    assert set().union(*(own for _, own in _TABLES.values())) == set(
        model_lib.TABLE_SCOPES)
    name, own = _TABLES.get(model, (None, set()))
    own = own - ({"kda_chunk", "gdn_chunk"} if which == "decode_window"
                 else {"kda_recurrent", "gdn_recurrent"})
    # no layer of the hybrid keeps K and V: "attention" is only what a
    # kernel's decode program computes its rows' lengths under
    lacks = {"attention"} if (model in ("hybrid", "shortcut")
                              and which == "packed_prefill") else set()
    if model == "gated_delta":       # no layer of it has a rotary embedding
        lacks = lacks | {"rope"}
    text = _lowered_step_program(
        which, _table_model(name) if name else None).as_text(debug_info=True)
    seen = set()
    for op_name in re.findall(r'loc\("(jit\([^"]*)"', text):
        seen.update(op_name.split("/"))
    want = [s for s in model_lib.SCOPES
            if s in own or s not in set(model_lib.TABLE_SCOPES) | lacks]
    missing = [s for s in want if s not in seen]
    assert not missing, f"{which}: no op carries scope(s) {missing}"
    assert not seen & (set(model_lib.TABLE_SCOPES) - own)


def test_the_routing_counters_ride_one_row_in_their_own_order():
    """``moe_stats_row`` sums every counter of ``MOE_STATS`` over the
    window's sparse layers but the load, of which it keeps the largest; the
    engine reads the row back by the same tuple, and a ``StepRecord`` has a
    field for each (``moe_pairs_zero`` since PR 41; ``moe_pairs_overflow``
    since PR 43, behind the load: a row of five, from calls over all their
    pairs, is the row it was and reads 0 there)."""
    from dynamo_tpu.observability.stepstats import StepRecord
    from dynamo_tpu.parallel.moe import MOE_STATS

    assert MOE_STATS[4:] == ("moe_load_max", "moe_pairs_overflow")
    assert "moe_pairs_zero" in MOE_STATS
    fields = {f.name for f in dataclasses.fields(StepRecord)}
    assert set(MOE_STATS) <= fields
    stats = [jnp.asarray([96, 10, 7, 30, 3], jnp.int32),
             jnp.asarray([96, 14, 9, 34, 5], jnp.int32)]
    row = np.asarray(model_lib.moe_stats_row(stats, 8))
    assert row.tolist() == [[192, 24, 16, 64, 5, 0, 0, 0]]
    rec = StepRecord(kind="decode", t_dispatch=0.0)
    for name, v in zip(MOE_STATS, row[0]):
        setattr(rec, name, int(v))
    assert (rec.moe_pairs_zero, rec.moe_load_max) == (64, 5)
    assert rec.moe_pairs_overflow == 0
    with pytest.raises(ValueError, match="routing counters"):
        model_lib.moe_stats_row(stats, 4)


def _host_event_names(trace_dir):
    import glob

    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert files, f"no .xplane.pb under {trace_dir}"
    names = set()
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


@pytest.mark.anyio
@pytest.mark.parametrize("python", [0, 1])
async def test_profile_of_serving_engine_names_phases(tmp_path, python):
    """A /debug/profile of a serving engine holds the engine-loop phases
    as host events on the profiler's clock; Python frames ('$file:line fn'
    events of the profiler's Python tracer) only with ``?python=1``. The
    engine probe reports the capture as ``last_profile``."""
    import asyncio
    import time

    import aiohttp

    from dynamo_tpu.runtime.system_server import SystemServer

    engine = InferenceEngine(ModelConfig.tiny(), _tiny_engine_config())
    server = SystemServer(metrics=MetricsRegistry(), host="127.0.0.1",
                          port=0)
    await engine.start()
    await server.start()
    try:
        captured = asyncio.Event()

        async def traffic(tag):
            i = 0
            while i == 0 or not captured.is_set():
                i += 1
                req = Request(request_id=f"{tag}{i}",
                              token_ids=[3 + i % 9, 1, 4],
                              max_tokens=24, ignore_eos=True)
                async for _ in engine.submit(req):
                    pass

        captured.set()
        await traffic("warm")   # one request: compile outside the capture
        captured.clear()
        task = asyncio.create_task(traffic("p"))
        t_before = time.monotonic()
        async with aiohttp.ClientSession() as sess:
            async with sess.get(
                    f"http://127.0.0.1:{server.port}/debug/profile",
                    params={"ms": "200", "dir": str(tmp_path),
                            "python": str(python)}) as resp:
                assert resp.status == 200
                data = await resp.json()
        captured.set()
        await task
        names = _host_event_names(data["trace_dir"])
        for phase in ("engine.schedule", "engine.dispatch", "engine.fetch",
                      "engine.postprocess"):
            assert phase in names, f"{phase} missing from the host planes"
        frames = [n for n in names if n.startswith("$")]
        assert bool(frames) == bool(python), frames[:5]
        last = engine.device_report()["last_profile"]
        assert last["trace_dir"] == data["trace_dir"]
        assert last["captured_ms"] >= 200
        assert t_before <= last["t0_mono"] <= time.monotonic()
        assert abs(last["t0_unix"] - time.time()) < 60
    finally:
        await server.stop()
        await engine.stop()


@pytest.mark.anyio
async def test_step_records_carry_host_seconds(tmp_path, monkeypatch):
    """host_s / dispatch_s / unpack_s are non-negative, survive the JSONL
    round trip, and account for the loop: sum(host_s) plus the loop's three
    wait totals is its wall time (to the last handoff)."""
    path = tmp_path / "steps.jsonl"
    monkeypatch.setenv("DYNTPU_OBS_STEPSTATS_PATH", str(path))
    engine = InferenceEngine(ModelConfig.tiny(), _tiny_engine_config())
    await engine.start()
    try:
        for prompt in ([5, 6, 7, 8, 9], [9, 8, 7], [1, 2, 3, 4, 5, 6]):
            assert len(await _run(engine, prompt)) == 4
        clock = engine.loop_clock
        snap = engine.obs_snapshot()
    finally:
        await engine.stop()    # closes the recorder: buffered lines land
    with open(path) as fh:
        records = load_records(fh)
    assert records and len(records) == snap["total_steps"]
    for r in records:
        assert r["host_s"] >= 0 and r["dispatch_s"] > 0 and r["unpack_s"] >= 0
    decode = [r for r in records if r["kind"] == DECODE]
    assert decode and all(r["host_s"] > 0 and r["unpack_s"] > 0
                          for r in decode)
    wall = clock.t_handoff - clock.t_start
    accounted = sum(r["host_s"] for r in records) + sum(
        clock.wait_s.values())
    assert set(clock.wait_s) == {"land", "idle", "executor"}
    assert accounted == pytest.approx(wall, rel=0.05)
    assert 0.0 < snap["host_busy_ratio"] <= 1.0
    gauges = EngineObsGauges(MetricsRegistry(), engine)
    assert gauges.refresh()["host_busy_ratio"] == snap["host_busy_ratio"]


@pytest.mark.parametrize("kv_tile,pages", [(128, 8), (16, 1), (32, 2),
                                           (8, 1)])
def test_kv_blocks_walked_counts_tiles_of_whole_pages(kv_tile, pages):
    """A hand-made batch: the sum over live rows of cdiv(ctx, tile) x pages
    per step, nothing for a dead row, and no table width anywhere."""
    from dynamo_tpu.observability.stepstats import kv_blocks_walked

    contexts = [1, 128, 129, 266, 0, 3000, 0, 16, 17]
    expect = sum(-(-c // kv_tile) * pages for c in contexts)
    assert kv_blocks_walked(contexts, kv_tile=kv_tile,
                            block_size=16) == expect
    assert kv_blocks_walked([0, 0], kv_tile=kv_tile, block_size=16) == 0
    assert kv_blocks_walked([], kv_tile=kv_tile, block_size=16) == 0
    if kv_tile == 128:   # by hand: 8 + 8 + 16 + 24 + 0 + 192 + 0 + 8 + 8
        assert expect == 264


@pytest.mark.anyio
@pytest.mark.parametrize("impl", ["pallas", "einsum"])
async def test_decode_records_carry_kv_blocks_walked(
        impl, tmp_path, monkeypatch):
    """The engine's decode records: with the Pallas decode class, pages per
    layer from each row's context and the tile the window was traced with,
    whatever the table's width (``max_model_len``); with the einsum class,
    which gathers every column, rows x width."""
    from dynamo_tpu.ops.paged_attention import default_kv_tile

    walked = {}
    for max_len in (128, 512):
        path = tmp_path / f"steps-{max_len}.jsonl"
        monkeypatch.setenv("DYNTPU_OBS_STEPSTATS_PATH", str(path))
        cfg = dataclasses.replace(_tiny_engine_config(attention_impl=impl),
                                  max_model_len=max_len)
        engine = InferenceEngine(ModelConfig.tiny(), cfg)
        await engine.start()
        try:
            for prompt in ([5, 6, 7, 8, 9], [9, 8, 7]):
                assert len(await _run(engine, prompt, n=6)) == 6
        finally:
            await engine.stop()
        with open(path) as fh:
            records = load_records(fh)
        decode = [r for r in records if r["kind"] == DECODE]
        assert len(decode) >= 8
        tile = engine._decode_kv_tile
        if impl == "pallas":
            # what the window was traced with: the kernel's default for the
            # tiny model's shapes, 64 pages of 4
            assert model_lib.ATTENTION_TRACES["decode"]["tile"] == [1, tile]
            assert tile == default_kv_tile(4, 8, 8, jnp.float32) == 256
        else:
            assert tile == 0
        for r in decode:
            assert r["live_rows"] == 1 and r["context_sum"] > 0
            if impl == "pallas":
                # one live row of context ``context_sum`` (K = 1); the
                # seven dead seats of the bucket walk nothing
                assert r["kv_blocks_walked"] == \
                    -(-r["context_sum"] // tile) * (tile // cfg.block_size)
            else:
                assert r["kv_blocks_walked"] == \
                    r["rows"] * cfg.max_blocks_per_seq
        assert all(r["kv_blocks_walked"] == 0 for r in records
                   if r["kind"] != DECODE)
        walked[max_len] = [r["kv_blocks_walked"] for r in decode]
    assert (walked[128] == walked[512]) == (impl == "pallas")


def test_kv_pages_written_counts_the_pages_rows_fall_on():
    """By hand at block 16: (start, count) -> pages."""
    from dynamo_tpu.observability.stepstats import kv_pages_written

    by_hand = {(0, 256): 16, (5, 256): 17, (15, 2): 2, (16, 16): 1,
               (31, 1): 1, (0, 0): 0, (100, 0): 0, (7, 9): 1, (7, 10): 2,
               (3, 512): 33}
    for row, pages in by_hand.items():
        assert kv_pages_written([row], block_size=16) == pages, row
    assert kv_pages_written(list(by_hand), block_size=16) \
        == sum(by_hand.values())
    assert kv_pages_written([], block_size=16) == 0


@pytest.mark.anyio
@pytest.mark.parametrize("spec_k", [0, 3])
async def test_records_carry_kv_pages_written(spec_k, tmp_path, monkeypatch):
    """Prefill, decode and spec records: the pages one layer's write
    touches, against each row's start and count read back from the record
    (one live row: ``context_sum`` = n x start + n(n + 1) / 2)."""
    path = tmp_path / "steps.jsonl"
    monkeypatch.setenv("DYNTPU_OBS_STEPSTATS_PATH", str(path))
    kw = dict(spec_mode="ngram", spec_k=spec_k) if spec_k else {}
    cfg = _tiny_engine_config(attention_impl="einsum", **kw)
    engine = InferenceEngine(ModelConfig.tiny(), cfg)
    await engine.start()
    try:
        # 21 tokens: a chunk of the 16 bucket and one that starts mid-cache
        prompt = [5, 6, 7] * 7
        assert len(await _run(engine, prompt, n=9)) == 9
    finally:
        await engine.stop()
    with open(path) as fh:
        records = load_records(fh)
    bs = cfg.block_size
    seen = set()
    for r in records:
        n = r["real_tokens"] // max(r["live_rows"], 1)
        assert r["live_rows"] == 1 and n > 0
        start, rem = divmod(r["context_sum"] - n * (n + 1) // 2, n)
        assert rem == 0
        if r["kind"] == DECODE:
            want = n        # K steps, a page each
        else:
            assert r["kind"] == PREFILL or n == spec_k + 1
            want = (start + n - 1) // bs - start // bs + 1
        assert r["kv_pages_written"] == want, r
        seen.add(r["kind"])
    assert seen == {PREFILL, SPEC_VERIFY if spec_k else DECODE}
    prefill = [r for r in records if r["kind"] == PREFILL]
    assert [r["kv_pages_written"] for r in prefill] == [4, 2]


def test_stepstats_jsonl_is_buffered_and_complete_on_close(tmp_path):
    """No flush per record (one a second at most), nothing lost on
    close()."""
    path = tmp_path / "steps.jsonl"
    stats, clock = _mk_stats(jsonl_path=str(path))
    for i in range(5):
        stats.commit(StepRecord(kind=DECODE, t_dispatch=100.0 + i,
                                t_land=100.1 + i, padded_tokens=8,
                                real_tokens=2, goodput_tokens=2,
                                context_sum=12, host_s=0.001 * i))
    with open(path) as fh:
        assert len(load_records(fh)) < 5       # still in the buffer
    clock["t"] += 1.5                          # a second on: one flush
    stats.commit(StepRecord(kind=DECODE, t_dispatch=106.0, t_land=106.1,
                            padded_tokens=8, real_tokens=2,
                            goodput_tokens=2, context_sum=12))
    with open(path) as fh:
        assert len(load_records(fh)) == 6
    stats.commit(StepRecord(kind=DECODE, t_dispatch=107.0, t_land=107.1,
                            padded_tokens=8, real_tokens=2,
                            goodput_tokens=2, context_sum=12))
    stats.close()
    with open(path) as fh:
        records = load_records(fh)
    assert len(records) == 7
    assert [round(r["host_s"], 3) for r in records[:5]] == [
        0.0, 0.001, 0.002, 0.003, 0.004]


# ---------------------------------------------------------------------------
# The road between the socket and the engine (PR 39): the loop's own busy
# counter, loop-wide busy seconds on the step records, a token's wake on
# engine.decode, and the benchmark's readers of all of it
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_loop_busy_counter_reads_a_known_share():
    """A loop kept busy for 6 ms of every 20 (on an absolute schedule, so a
    late wake shortens the next sleep) reads 0.25-0.35 busy, and the runtime
    exports the same counter as ``event_loop_busy_seconds_total``. On a
    crowded machine a spin is preempted and holds the loop longer than its 6
    ms, so the test sums what its spins really held: the counter must agree
    with that sum whatever the machine does, and with the nominal 30% when
    the machine kept the schedule."""
    import asyncio
    import time

    from dynamo_tpu.runtime import loop_busy

    counter = loop_busy.install()
    assert counter is not None and loop_busy.install() is counter
    registry = MetricsRegistry(prefix="dynamo")
    registry.counter_fn("event_loop_busy_seconds", "doc", counter.busy_s)
    b0, t0 = counter.busy_s(), time.monotonic()
    held = 0.0
    for i in range(1, 51):
        t = time.monotonic()
        while time.monotonic() - t < 0.006:
            pass
        held += time.monotonic() - t
        await asyncio.sleep(max(0.0, t0 + 0.02 * i - time.monotonic()))
    wall = time.monotonic() - t0
    share = (counter.busy_s() - b0) / wall
    assert held / wall - 0.01 <= share <= held / wall + 0.05
    if held <= 0.32:
        assert 0.25 <= share <= 0.35
    [sample] = [s for s in validate_exposition(registry.render())
                if s.name == "dynamo_event_loop_busy_seconds_total"]
    assert sample.value >= counter.busy_s() - 1.0 and sample.value > 0.29


@pytest.mark.anyio
async def test_records_carry_loop_busy_and_decode_spans_the_wake(
        tmp_path, monkeypatch):
    """``loop_busy_s`` sits beside ``host_s`` on a batch's record: the
    engine-loop task is one of the loop's tasks, so ``host_s <=
    loop_busy_s`` a batch, and the loop cannot have been busy longer than
    the wall; ``loop_cpu_s`` is the part of it the loop thread was on the
    CPU. ``engine.decode`` carries the landed -> stream wake sums."""
    from dynamo_tpu import tracing
    from dynamo_tpu.runtime.context import Context

    path = tmp_path / "steps.jsonl"
    monkeypatch.setenv("DYNTPU_OBS_STEPSTATS_PATH", str(path))
    exporter = tracing.InMemorySpanExporter()
    tracer = tracing.reset()
    tracer.configure(sample_ratio=1.0)
    tracer.add_exporter(exporter)
    engine = InferenceEngine(ModelConfig.tiny(), _tiny_engine_config())
    await engine.start()
    try:
        for prompt in ([5, 6, 7, 8, 9], [9, 8, 7], [1, 2, 3, 4, 5, 6]):
            outs = [o async for o in engine.generate(
                {"token_ids": prompt, "max_tokens": 6, "ignore_eos": True},
                Context())]
            assert len(outs) == 6
        clock = engine.loop_clock
        snap = engine.obs_snapshot()
    finally:
        await engine.stop()
        tracing.reset()
    with open(path) as fh:
        records = load_records(fh)
    owners = [r for r in records if r["host_s"] > 0]
    assert owners and all(r["kind"] == DECODE or r is records[-1]
                          or r["loop_busy_s"] > 0 for r in owners)
    for r in records:
        assert 0.0 <= r["host_s"] <= r["loop_busy_s"] + 1e-6
        assert r["loop_cpu_s"] >= 0.0
    wall = clock.t_handoff - clock.t_start
    busy = sum(r["loop_busy_s"] for r in records)
    assert 0.0 < busy <= wall + 1e-6
    # two clocks, so a little slack: CPU seconds are busy seconds
    assert 0.0 < sum(r["loop_cpu_s"] for r in records) <= busy * 1.05 + 1e-3
    assert snap["host_busy_ratio"] <= snap["loop_busy_ratio"] <= 1.0
    gauges = EngineObsGauges(MetricsRegistry(), engine)
    assert gauges.refresh()["loop_busy_ratio"] == snap["loop_busy_ratio"]
    decode = [s for s in exporter.spans if s.name == "engine.decode"]
    assert len(decode) == 3
    for s in decode:
        # six tokens, each a wake; none can outlast the whole request
        assert 0.0 < s.attrs["wake_max_s"] <= s.attrs["wake_sum_s"]
        assert s.attrs["wake_sum_s"] <= 6 * s.attrs["wake_max_s"] + 1e-9
    # the assembler's road summary is their reader: mean over 18 tokens
    from dynamo_tpu.tracing.assemble import road_summary

    road = road_summary([s.to_dict() for s in exporter.spans])
    assert road["wake_mean_us"] == pytest.approx(
        1e6 * sum(s.attrs["wake_sum_s"] for s in decode) / 18)
    assert road["wake_max_ms"] == pytest.approx(
        1e3 * max(s.attrs["wake_max_s"] for s in decode))


def test_loop_clock_handoff_closes_both_clocks_at_one_instant(monkeypatch):
    """``host_s`` and ``loop_busy_s`` of a handoff end at the same reading of
    the clock. Read twice, a thread switch between the reads went to this
    handoff's ``loop_busy_s`` and came off the next one's, which then fell
    short of its ``host_s`` (the load failure of the test above)."""
    import time

    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.runtime import loop_busy

    class Clock:
        """``time`` with a scripted ``monotonic``: ``drift`` seconds pass
        behind every reading (the thread was switched out)."""
        t, drift = 100.0, 0.0

        def monotonic(self):
            now = self.t
            self.t += self.drift
            return now

        def __getattr__(self, name):
            return getattr(time, name)

    clock = Clock()
    monkeypatch.setattr(engine_mod, "time", clock)
    monkeypatch.setattr(loop_busy, "time", clock)
    loop_clock = engine_mod._LoopClock(loop_busy.LoopBusyCounter())
    clock.t += 2.0
    clock.drift = 5.0           # switched out behind each reading
    assert loop_clock.handoff()[:2] == (2.0, 2.0)
    clock.drift = 0.0
    clock.t += 3.0
    host_s, loop_busy_s, _ = loop_clock.handoff()
    assert host_s == 8.0        # the 5 s it was switched out, then 3 s
    assert loop_busy_s == host_s


def _reader(name):
    from benchmarks.chip.run import load_reader

    return load_reader(name).read


def test_road_readers_on_a_hand_made_window():
    """The eight readers of PR 39 on spans and step records written by
    hand: each value, and each None (no spans, no counter, ``decode_steps``
    above 1, a parent that stamps nothing)."""
    hist = {"lo_s": 1e-3, "ratio": 2 ** 0.25, "counts": [0] * 50}

    def ingress(trace, start, end, upstream, submitted, first_sent, counts):
        h = dict(hist, counts=list(hist["counts"]))
        for i, n in counts.items():
            h["counts"][i] = n
        return {"name": "worker.ingress", "trace_id": trace,
                "start_mono": start, "end_mono": end,
                "attrs": {"upstream_s": upstream, "wire_s": 0.0002,
                          "frames": 1 + sum(counts.values()),
                          "sent_gaps": h},
                "events": [{"offset_s": first_sent, "name": "first_sent"}]
                }, {"name": "worker.queue", "trace_id": trace,
                    "start_mono": start + submitted,
                    "end_mono": start + submitted + 0.007}

    def prefill(trace, end):
        return {"name": "engine.prefill", "trace_id": trace,
                "start_mono": end - 0.04, "end_mono": end}

    # window [100, 150): four requests whose first frame left inside it
    # (way in 3.0, 5.0, 4.0, 8.0 ms; emit -> sent 0.5, 0.9, 0.7, 0.5 ms),
    # the last of them still running at its close; one whose first frame
    # left before it
    spans = [
        *ingress("a", 101.0, 110.0, 0.0025, 0.0005, 0.050, {17: 90, 25: 10}),
        prefill("a", 101.0495),
        *ingress("b", 120.0, 130.0, 0.0040, 0.0010, 0.060, {17: 95, 29: 5}),
        prefill("b", 120.0591),
        *ingress("c", 140.0, 149.0, 0.0036, 0.0004, 0.055, {17: 100}),
        prefill("c", 140.0543),
        *ingress("early", 99.0, 105.0, 0.9, 0.1, 0.5, {40: 3}),
        prefill("early", 99.4),
        *ingress("late", 149.5, 160.0, 0.0070, 0.0010, 0.052, {45: 50}),
        prefill("late", 149.5515),
    ]

    def decode(t_land, live, **kw):
        return {"kind": "decode", "t_dispatch": t_land - 0.02,
                "t_land": t_land, "rows": 64, "live_rows": live,
                "padded_tokens": 64, "real_tokens": live, **kw}

    steps = [decode(100.000, 10, host_s=0.001, loop_busy_s=0.004),
             decode(100.016, 10, host_s=0.001, loop_busy_s=0.005),
             decode(100.032, 10, host_s=0.001, loop_busy_s=0.006),
             {"kind": "prefill", "t_dispatch": 100.03, "t_land": 100.05,
              "rows": 1, "live_rows": 1, "padded_tokens": 512,
              "real_tokens": 384},
             {"kind": "prefill", "t_dispatch": 100.05, "t_land": 100.07,
              "rows": 1, "live_rows": 1, "padded_tokens": 512,
              "real_tokens": 512},
             decode(100.082, 1, host_s=0.002, loop_busy_s=0.010)]
    ctx = {"window": (100.0, 150.0), "spans": spans, "steps": steps}
    assert _reader("ingress_lag_p50_ms")(ctx) == pytest.approx(4.5)
    assert _reader("first_emit_lag_p50_ms")(ctx) == pytest.approx(0.6)
    # streams a, b, c ended in the window: 285 gaps in bucket 17, 10 in 25,
    # 5 in 29; the 95th percentile (the 285.25th of 300) is in bucket 25
    edge = 2 ** (25 / 4)
    assert _reader("sent_gap_p95_ms")(ctx) == pytest.approx(edge)
    assert _reader("sent_gap_p95_ms.longgen")(ctx) == pytest.approx(edge)
    # three landing gaps: 16 ms (10 rows), 16 ms (10 rows), 50 ms (1 row);
    # 95% of 21 row-gaps is reached inside the second
    assert _reader("land_gap_p95_ms")(ctx) == pytest.approx(16.0)
    assert _reader("land_gap_p95_ms.longgen")(ctx) == pytest.approx(16.0)
    steps[-1]["live_rows"] = 10      # the long gap now weighs a third
    assert _reader("land_gap_p95_ms")(ctx) == pytest.approx(50.0)
    assert _reader("worker_loop_busy_share")(ctx) == pytest.approx(
        100 * 0.025 / 50)
    assert _reader("prefill_real_token_share")(ctx) == pytest.approx(87.5)

    # a decode window of more than one step: a landing is several tokens
    multi = dict(ctx, steps=[dict(s, padded_tokens=128) if s["kind"] ==
                             "decode" else s for s in steps])
    assert _reader("land_gap_p95_ms")(multi) is None
    # a parent of PR 39: spans without the attrs and events, records
    # without the counter; and a run that kept nothing at all
    bare = [{k: v for k, v in s.items() if k not in ("attrs", "events")}
            for s in spans]
    old = {"window": (100.0, 150.0), "spans": bare,
           "steps": [{k: v for k, v in s.items() if k != "loop_busy_s"}
                     for s in steps]}
    empty = {"window": (100.0, 150.0), "spans": [], "steps": []}
    for name in ("ingress_lag_p50_ms", "first_emit_lag_p50_ms",
                 "sent_gap_p95_ms", "sent_gap_p95_ms.longgen",
                 "worker_loop_busy_share"):
        assert _reader(name)(old) is None and _reader(name)(empty) is None
    assert _reader("land_gap_p95_ms")(old) == pytest.approx(50.0)
    assert _reader("prefill_real_token_share")(old) == pytest.approx(87.5)
    for name in ("land_gap_p95_ms", "land_gap_p95_ms.longgen",
                 "prefill_real_token_share"):
        assert _reader(name)(empty) is None
