"""Trace-replay scoreboard suite.

Fast seeded units (trace generation, JSONL round-trip, storm windows,
ground-truth arithmetic, scoreboard cross-check teeth) plus THE acceptance
run: a bursty multi-tenant trace — shared-prefix pools, a deadline-tier
pair, a mid-run maintenance preemption, an abort storm — replayed twice
against a real-engine SimCluster; both runs must pass every cross-check
and produce identical request-level outcome digests.

Every cluster test prints ``REPLAY_SEED=<n>`` so a failing run reproduces
with ``DYNTPU_REPLAY_SEED=<n> scripts/verify.sh replay``.
"""

import json
import os

import pytest

from benchmarks.datagen import (
    GeneratedRequest, PrefixDatasetConfig, RequestRecord,
    generate_prefix_dataset, prefix_ground_truth, summarize,
)
from benchmarks.loadgen import assign_tiers
from dynamo_tpu.replay.driver import (
    ReplaySettings, RequestOutcome, run_cluster_replay,
)
from dynamo_tpu.replay.scoreboard import (
    CheckTolerances, build_scoreboard, cross_check_tokens, cross_check_ttft,
    outcome_digest,
)
from dynamo_tpu.replay.trace import (
    TraceConfig, dump_jsonl, generate_trace, load_jsonl,
)
from dynamo_tpu.tracing.assemble import stage_percentiles

pytestmark = [pytest.mark.replay]

REPLAY_SEED = int(os.environ.get("DYNTPU_REPLAY_SEED", "7"))


@pytest.fixture
def anyio_backend():
    return "asyncio"


# ----------------------------- trace units ------------------------------


def _storm_cfg(seed=3):
    return TraceConfig(
        seed=seed, num_requests=70, duration_s=5.0,
        abort_storm_start_frac=0.2, abort_storm_end_frac=0.5,
        reconnect_storm_start_frac=0.6, reconnect_storm_end_frac=0.9,
        preempt_at_frac=0.4, store_flap_at_frac=0.8,
    )


def test_trace_same_seed_identical():
    a, b = generate_trace(_storm_cfg()), generate_trace(_storm_cfg())
    assert [r.__dict__ for r in a.requests] == [r.__dict__ for r in b.requests]
    assert [e.__dict__ for e in a.events] == [e.__dict__ for e in b.events]
    assert a.meta == b.meta


def test_trace_seed_changes_trace():
    a, b = generate_trace(_storm_cfg(3)), generate_trace(_storm_cfg(4))
    assert [r.token_ids for r in a.requests] != [r.token_ids for r in b.requests]


def test_trace_jsonl_roundtrip(tmp_path):
    a = generate_trace(_storm_cfg())
    path = str(tmp_path / "trace.jsonl")
    dump_jsonl(a, path)
    b = load_jsonl(path)
    assert [r.__dict__ for r in a.requests] == [r.__dict__ for r in b.requests]
    assert [e.__dict__ for e in a.events] == [e.__dict__ for e in b.events]
    assert a.meta == b.meta
    assert a.tiers() == b.tiers()


def test_storm_windows_and_exclusivity():
    cfg = _storm_cfg()
    trace = generate_trace(cfg)
    aborts = [r for r in trace.requests if r.abort_after_tokens is not None]
    recons = [r for r in trace.requests
              if r.reconnect_after_tokens is not None]
    assert aborts and recons
    for r in aborts:
        assert 0.2 * cfg.duration_s <= r.arrival_s < 0.5 * cfg.duration_s
        assert r.reconnect_after_tokens is None  # mutually exclusive
    for r in recons:
        assert 0.6 * cfg.duration_s <= r.arrival_s < 0.9 * cfg.duration_s
    kinds = [e.kind for e in trace.events]
    assert kinds == ["preempt", "store_flap"]  # sorted by at_s


def test_trace_tenant_pools_do_not_alias():
    trace = generate_trace(TraceConfig(seed=1, num_requests=30))
    by_tenant = {}
    for r in trace.requests:
        if r.pool >= 0:
            by_tenant.setdefault(r.tenant, set()).add(tuple(r.token_ids[:8]))
    tenants = list(by_tenant)
    assert len(tenants) == 2
    assert not (by_tenant[tenants[0]] & by_tenant[tenants[1]])


def test_outliers_have_unique_prompts_and_no_pool():
    trace = generate_trace(TraceConfig(
        seed=2, num_requests=40, outlier_ratio=0.3, outlier_isl=64))
    outliers = [r for r in trace.requests if r.pool == -1]
    assert outliers
    assert all(r.isl == 64 for r in outliers)
    prompts = [tuple(r.token_ids) for r in outliers]
    assert len(set(prompts)) == len(prompts)


# ----------------------- datagen ground truth ---------------------------


def test_prefix_ground_truth_arithmetic():
    ds = generate_prefix_dataset(PrefixDatasetConfig(
        num_requests=16, isl=32, prefix_ratio=0.5, groups=2, branches=2,
        seed=5))
    gt = prefix_ground_truth(ds)
    assert gt["total_prompt_tokens"] == sum(len(r.token_ids) for r in ds)
    # every request carries its group+branch shared tokens; dedup keeps one
    # copy per group and per (group, branch)
    assert gt["shared_tokens_total"] == sum(
        r.group_len + r.branch_len for r in ds)
    assert 0 < gt["shared_tokens_dedup"] < gt["shared_tokens_total"]
    assert gt["prefix_hit_potential_tokens"] == (
        gt["shared_tokens_total"] - gt["shared_tokens_dedup"])


def test_summarize_emits_tier_blocks_and_ground_truth():
    ds = generate_prefix_dataset(PrefixDatasetConfig(
        num_requests=8, isl=16, prefix_ratio=0.5, seed=1))
    records = []
    for i in range(8):
        rec = RequestRecord(start=0.0, tier=i % 2)
        rec.ttft = 0.1 + 0.01 * i
        rec.itls = [0.01, 0.02]
        rec.output_tokens = 4
        rec.end = 0.5
        records.append(rec)
    out = summarize(records, elapsed_s=2.0, dataset=ds)
    assert set(out["tiers"]) == {"0", "1"}
    assert out["tiers"]["0"]["requests"] == 4
    assert out["tiers"]["0"]["ttft_p50_ms"] > 0
    assert out["prefix_hit_potential_tokens"] == (
        out["shared_tokens_total"] - out["shared_tokens_dedup"])


def test_assign_tiers_seeded_and_optional():
    assert assign_tiers(4, []) == [None, None, None, None]
    a = assign_tiers(100, [0.5, 0.5], seed=3)
    assert a == assign_tiers(100, [0.5, 0.5], seed=3)
    assert set(a) == {0, 1}
    assert assign_tiers(100, [0.5, 0.5], seed=4) != a


# ------------------------- assemble --summary ---------------------------


def test_stage_percentiles_from_span_dicts():
    spans = ([{"name": "worker.queue", "duration_s": 0.01 * i}
              for i in range(1, 101)]
             + [{"name": "engine.prefill", "duration_s": 0.5}])
    stages = stage_percentiles(spans)
    assert stages["worker.queue"]["count"] == 100
    assert stages["worker.queue"]["p50_ms"] == pytest.approx(500, rel=0.05)
    assert stages["worker.queue"]["p99_ms"] == pytest.approx(1000, rel=0.05)
    assert stages["engine.prefill"]["max_ms"] == pytest.approx(500)


# ----------------------- scoreboard cross-checks ------------------------


def _outcome(rid="r0", trace_id="t0", ttft=0.2, tokens=(5, 6, 7),
             submissions=((10, 3),), **kw):
    out = RequestOutcome(
        request_id=rid, tenant="tenant0", pool=0, tier=0, isl=10, osl=3,
        arrival_s=0.0, trace_id=trace_id, ttft_s=ttft,
        tokens=list(tokens), finish_reason="length",
        submissions=[list(s) for s in submissions], **kw)
    return out


def _spans(trace_id="t0", queue=0.05, prefill=0.1):
    return [
        {"name": "worker.queue", "trace_id": trace_id, "duration_s": queue},
        {"name": "engine.prefill", "trace_id": trace_id,
         "duration_s": prefill},
    ]


def test_ttft_check_passes_on_consistent_timeline():
    chk = cross_check_ttft([_outcome()], _spans(), CheckTolerances())
    assert chk["ok"] and chk["samples"] == 1


def test_ttft_check_fails_when_span_exceeds_client():
    # span-assembled worker time longer than the client saw ⇒ the
    # instrumentation is lying about where the time went
    chk = cross_check_ttft(
        [_outcome(ttft=0.1)], _spans(queue=0.2, prefill=0.2),
        CheckTolerances())
    assert not chk["ok"] and "exceeds client" in chk["reason"]


def test_ttft_check_fails_without_samples():
    chk = cross_check_ttft([_outcome()], [], CheckTolerances())
    assert not chk["ok"] and "span pipeline" in chk["reason"]


def test_ttft_check_skips_dirty_requests():
    dirty = _outcome(rid="r1", trace_id="t1")
    dirty.resumes = 1
    chk = cross_check_ttft(
        [_outcome(), dirty], _spans() + _spans("t1"), CheckTolerances())
    assert chk["samples"] == 1


def test_token_check_brackets_recorder():
    outs = [_outcome(submissions=((10, 3),))]  # client expects 13
    tol = CheckTolerances(token_tol_low=0.05, token_tol_high=0.5)
    assert cross_check_tokens(outs, 13.0, 0.0, tol)["ok"]
    # prefix hits credit the lower bound
    assert cross_check_tokens(outs, 9.0, 4.0, tol)["ok"]
    low = cross_check_tokens(outs, 5.0, 0.0, tol)
    assert not low["ok"] and "below bound" in low["reason"]
    high = cross_check_tokens(outs, 40.0, 0.0, tol)
    assert not high["ok"] and "amplification" in high["reason"]


def test_outcome_digest_sensitivity():
    a, b = _outcome(), _outcome()
    assert outcome_digest([a]) == outcome_digest([b])
    b.tokens = [5, 6, 8]
    assert outcome_digest([a]) != outcome_digest([b])
    b.tokens = [5, 6, 7]
    b.aborted = True
    assert outcome_digest([a]) != outcome_digest([b])


# --------------------- THE acceptance cluster run -----------------------


def _acceptance_cfg(seed: int) -> TraceConfig:
    """Bursty multi-tenant trace: shared-prefix pools, one deadline-tier
    pair, an abort storm, and a mid-run maintenance preemption."""
    return TraceConfig(
        seed=seed, num_requests=24, duration_s=3.0, base_rps=10.0,
        burst_factor=3.0, tenants=2, pools_per_tenant=2,
        abort_storm_start_frac=0.3, abort_storm_end_frac=0.6,
        preempt_at_frac=0.45,
    )


async def _replay_once(seed: int, workdir: str) -> dict:
    trace = generate_trace(_acceptance_cfg(seed))
    run = await run_cluster_replay(
        trace, ReplaySettings(time_scale=4.0), workdir=workdir)
    return build_scoreboard(trace, run)


@pytest.mark.anyio
async def test_cluster_replay_scoreboard_and_determinism(tmp_path):
    print(f"REPLAY_SEED={REPLAY_SEED}")
    rep1 = await _replay_once(REPLAY_SEED, str(tmp_path / "a"))
    rep2 = await _replay_once(REPLAY_SEED, str(tmp_path / "b"))

    # every headline metric present and sane
    for rep in (rep1, rep2):
        assert rep["requests"] == 24
        assert rep["errors"] == 0
        assert rep["aborted"] > 0                      # storm hit
        assert rep["completed"] + rep["aborted"] == rep["requests"]
        assert set(rep["tiers"]) == {"0", "1"}
        for row in rep["tiers"].values():
            assert row["ttft_p50_ms"] > 0
            assert row["itl_p99_ms"] >= row["itl_p50_ms"]
            assert row["slo_violation_rate"] is not None
        assert rep["prefix_hit_rate"] is not None and rep["prefix_hit_rate"] > 0
        assert rep["chip_seconds_per_1m_output_tokens"] > 0
        # CPU run: no published peak, so no roofline figure
        assert rep["ideal_chip_seconds_per_1m_output_tokens"] is None
        # preemption fired and was accounted
        assert rep["preempt"]["notices"] == 1
        assert [e["kind"] for e in rep["events_fired"]] == ["preempt"]
        # the observability teeth: both cross-checks within tolerance
        assert rep["checks"]["ttft_vs_spans"]["ok"], rep["checks"]
        assert rep["checks"]["tokens_vs_recorder"]["ok"], rep["checks"]
        assert rep["ok"]

    # same seed ⇒ identical request-level outcomes
    assert rep1["outcome_digest"] == rep2["outcome_digest"]
    # report is JSON-serializable as written by the CLI
    json.dumps(rep1)


@pytest.mark.anyio
@pytest.mark.slow
async def test_flagship_replay(tmp_path):
    """Flagship: outliers, abort + reconnect storms, preempt + store flap,
    3 tenants — everything at once, still reproducible and cross-checked."""
    print(f"REPLAY_SEED={REPLAY_SEED}")
    from dynamo_tpu.replay.__main__ import scenario_config

    trace = generate_trace(scenario_config("flagship", REPLAY_SEED))
    run = await run_cluster_replay(
        trace, ReplaySettings(time_scale=4.0, n_workers=2),
        workdir=str(tmp_path))
    rep = build_scoreboard(trace, run)
    assert rep["requests"] == 96
    assert rep["errors"] == 0
    assert rep["aborted"] > 0
    assert rep["reconnects"] > 0
    assert {e["kind"] for e in rep["events_fired"]} == {
        "preempt", "store_flap"}
    assert rep["ok"], rep["checks"]
