"""The attention kernel's parity gate and the grid of tiles it walks.

The headline test re-runs ``engine.attention_parity``'s CPU selftest in a
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

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.attention_parity import (
    class_shapes,
    make_sweep_case,
    parity_check,
    tile_candidates,
)
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
# the gate: every tile of the grid bit-exact against its own reference


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
        [sys.executable, "-m", "dynamo_tpu.engine.attention_parity"],
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
