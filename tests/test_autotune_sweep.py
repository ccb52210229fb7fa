"""Kernel tile autotune: parity gate, candidate grids, persisted cache.

The headline test re-runs the autotune module's CPU parity selftest in a
subprocess with the XLA fusion pass disabled: that is the ONLY process
configuration under which the order-exact jnp reference and the
interpret-mode Pallas kernel are bit-identical (XLA re-fuses the eager
reference's mul/add chains differently inside jit, a 1-ulp drift), and
XLA flags parse once per process — so the bitwise gate cannot run inside
the main pytest process once any other test has initialized the backend.
"""

import json
import os
import subprocess
import sys

import pytest

from dynamo_tpu.engine.autotune import (
    CACHE_VERSION,
    autotune_attention,
    cache_path,
    class_shapes,
    config_hash,
    load_cache_entry,
    make_sweep_case,
    parity_check,
    store_cache_entry,
    tile_candidates,
)
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.ops.paged_attention import default_kv_tile

pytestmark = pytest.mark.tune


def _cfgs(**over):
    eng = dict(
        block_size=16, num_blocks=128, max_num_seqs=8,
        max_num_batched_tokens=256, max_model_len=256,
        decode_buckets=(8,), prefill_buckets=(16, 32),
        spec_mode="ngram", spec_k=3,
    )
    eng.update(over)
    return ModelConfig.tiny(), EngineConfig(**eng)


# ---------------------------------------------------------------------------
# the acceptance gate: every candidate bit-exact before eligibility


def test_parity_selftest_every_candidate_bitwise():
    """scripts/verify.sh tune: all (q_tile, kv_tile) candidates of all
    three shape classes must match the order-exact reference bit-for-bit
    on CPU (interpret mode, fusion disabled) over mixed ragged batches
    with NaN-poisoned trash blocks and partial tails."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_disable_hlo_passes=fusion",
    )
    out = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.engine.autotune"],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout)
    assert report["fusion_disabled"] is True
    rows = [r for cls in report["classes"].values() for r in cls]
    assert len(rows) >= 8  # decode + spec + prefill grids are non-trivial
    bad = [r for r in rows if not (r["bitwise"] and r["eligible"])]
    assert not bad, f"candidates failed the bitwise gate: {bad}"
    assert report["all_eligible"] is True
    # the default config is always a candidate in every class
    for cls_rows in report["classes"].values():
        assert (cls_rows[0]["q_tile"], cls_rows[0]["kv_tile"]) == (0, 0)


def test_parity_check_catches_a_mismasking_candidate(monkeypatch):
    """The gate itself must have teeth: a kv_tile that does not divide
    block_size raises instead of silently computing garbage, and the
    NaN-poisoned case flags any output that touched a trash block."""
    mc, ec = _cfgs()
    case = make_sweep_case(mc, ec, "prefill", 4, 16)
    with pytest.raises(ValueError, match="kv_tile"):
        parity_check(case, 0, 3)


# ---------------------------------------------------------------------------
# candidate grids


def test_tile_candidates_respect_shape_and_sublane_rules():
    mc, ec = _cfgs()
    # decode (T=1): no q_tile axis, only the KV walk's tile
    dec = tile_candidates(mc, ec, "decode", 1)
    assert dec[0] == (0, 0)
    assert all(qt == 0 for qt, _ in dec)
    # a kv_tile is a divisor of block_size that respects the f32 sublane
    # min, or whole pages: half and twice the default's pages per step
    default = default_kv_tile(ec.block_size, mc.num_kv_heads, mc.head_dim_,
                              mc.dtype)
    assert default % ec.block_size == 0 and default >= ec.block_size
    kts = [kt for _, kt in dec if kt]
    assert [kt for kt in kts if kt >= ec.block_size] == [
        default // 2, default * 2]
    for kt in kts:
        if kt < ec.block_size:
            assert ec.block_size % kt == 0 and kt >= 8
    # prefill: q_tiles divide T and exclude the default
    pre = tile_candidates(mc, ec, "prefill", 32)
    assert pre[0] == (0, 0)
    for qt, _ in pre:
        if qt:
            assert 32 % qt == 0 and qt != 32
    # bf16 raises the sublane floor to 16: kv_tile 8 disappears
    import dataclasses
    mc16 = dataclasses.replace(ModelConfig.tiny(), dtype="bfloat16")
    kts = {kt for _, kt in tile_candidates(mc16, ec, "decode", 1)}
    assert 8 not in kts


def test_class_shapes_follow_engine_config():
    mc, ec = _cfgs()
    shapes = class_shapes(mc, ec)
    assert shapes["decode"] == (8, 1)
    assert shapes["spec"] == (8, 4)
    assert shapes["prefill"] == (4, 32)
    _, ec_off = _cfgs(spec_mode="off", spec_k=0)
    assert "spec" not in class_shapes(mc, ec_off)


# ---------------------------------------------------------------------------
# persisted tuning cache


def test_cache_roundtrip_and_corruption(tmp_path):
    path = str(tmp_path / "tune.json")
    entry = {"device_kind": "TPU v5 lite",
             "tiles": {"decode": [0, 8], "prefill": [16, 0]}}
    assert store_cache_entry(path, "k1", entry)
    got = load_cache_entry(path, "k1")
    assert got["tiles"]["decode"] == [0, 8]
    assert load_cache_entry(path, "other-key") is None
    # a second entry merges without clobbering the first
    assert store_cache_entry(path, "k2", {"tiles": {}})
    assert load_cache_entry(path, "k1") is not None
    # version drift and corruption both miss instead of raising
    doc = json.load(open(path))
    doc["version"] = CACHE_VERSION + 1
    json.dump(doc, open(path, "w"))
    assert load_cache_entry(path, "k1") is None
    open(path, "w").write("{not json")
    assert load_cache_entry(path, "k1") is None
    assert load_cache_entry(str(tmp_path / "absent.json"), "k1") is None


def test_config_hash_drift_invalidates(monkeypatch):
    """ISSUE 12 regression: any drift in model geometry, engine shape
    fields, or device kind changes the key, so a stale winner can never
    be replayed onto a different configuration."""
    mc, ec = _cfgs()
    base = config_hash(mc, ec, "TPU v5 lite")
    assert base == config_hash(*_cfgs(), "TPU v5 lite")  # deterministic
    import dataclasses
    drifted = [
        config_hash(mc, ec, "TPU v6e"),
        config_hash(mc, dataclasses.replace(ec, block_size=32), "TPU v5 lite"),
        config_hash(mc, dataclasses.replace(ec, decode_buckets=(8, 16)),
                    "TPU v5 lite"),
        config_hash(mc, dataclasses.replace(ec, spec_k=5), "TPU v5 lite"),
        config_hash(dataclasses.replace(mc, num_layers=mc.num_layers + 1),
                    ec, "TPU v5 lite"),
    ]
    assert len({base, *drifted}) == len(drifted) + 1


def test_autotune_attention_cache_precedence(tmp_path, monkeypatch):
    """Cache hit adopts the persisted tiles (even off-TPU — the entry is
    keyed to this exact config+device) and explicit config tiles beat
    the cache."""
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("DYNTPU_AUTOTUNE_CACHE", path)
    assert cache_path() == path
    mc, ec = _cfgs(attention_impl="einsum")

    # miss: defaults, autotune_cache_hit False
    cfg, choice = autotune_attention(mc, ec)
    assert choice["autotune_cache_hit"] is False
    assert cfg.attention_tile_decode == (0, 0)

    # seed the cache under the real key → hit adopts tiles
    store_cache_entry(path, choice["config_hash"], {
        "device_kind": "cpu",
        "tiles": {"decode": [0, 8], "spec": [1, 8], "prefill": [8, 8]},
    })
    cfg2, choice2 = autotune_attention(mc, ec)
    assert choice2["autotune_cache_hit"] is True
    assert cfg2.attention_tile_decode == (0, 8)
    assert cfg2.attention_tile_spec == (1, 8)
    assert cfg2.attention_tile_prefill == (8, 8)

    # explicit config tiles always win over the cache
    import dataclasses
    ec3 = dataclasses.replace(ec, attention_tile_prefill=(16, 0))
    cfg3, choice3 = autotune_attention(mc, ec3)
    assert choice3["autotune_cache_hit"] is True
    assert cfg3.attention_tile_prefill == (16, 0)
    assert cfg3.attention_tile_decode == (0, 8)  # cache still fills the rest


def test_autotune_attention_no_cache_no_tpu_is_defaults(monkeypatch):
    monkeypatch.delenv("DYNTPU_AUTOTUNE_CACHE", raising=False)
    mc, ec = _cfgs(attention_impl="einsum")
    cfg, choice = autotune_attention(mc, ec)
    assert choice["autotune_cache_hit"] is False
    assert choice["cache_path"] == ""
    assert choice["tiles"] == {
        "decode": [0, 0], "spec": [0, 0], "prefill": [0, 0]}
    assert cfg.attention_tile_decode == (0, 0)


# ---------------------------------------------------------------------------
# the device sweep's control flow, on CPU (kernel interpreted, clock faked)


@pytest.mark.parametrize("faster_by,expect", [(0.02, (0, 0)), (0.10, (0, 256))])
def test_device_sweep_sees_the_engines_table_and_keeps_the_default(
        monkeypatch, faster_by, expect):
    """The sweep times every candidate at a table as wide as the engine's
    own and at two context depths, gates each against the naive reference,
    and lets a candidate replace the default only past the margin."""
    import functools

    import dynamo_tpu.ops.paged_attention as pa
    from dynamo_tpu.engine import autotune

    monkeypatch.setattr(pa, "paged_attention_ragged", functools.partial(
        pa.paged_attention_ragged, interpret=True))
    seen = []

    def fake_clock(fn, args, iters=20):
        tables = args[3]
        seen.append(tuple(tables.shape))
        kv_tile = fn.__wrapped__.keywords["kv_tile"]
        return 1.0 - faster_by if kv_tile == 256 else 1.0

    monkeypatch.setattr(autotune, "_time_attention", fake_clock)
    mc, ec = _cfgs(max_model_len=4096, spec_mode="off", spec_k=0)
    res = autotune._sweep_class_device(mc, ec, "decode", 4, 1)
    assert res["width"] == ec.max_blocks_per_seq == 256
    assert res["depths"] == [384, 3072]
    assert set(seen) == {(4, 256)}            # never a narrower table
    assert [c["kv_tile"] for c in res["candidates"]] == [0, 8, 64, 256]
    assert all(c["eligible"] and set(c["ms"]) == {"ctx384", "ctx3072"}
               for c in res["candidates"])
    assert tuple(res["winner"]) == expect
