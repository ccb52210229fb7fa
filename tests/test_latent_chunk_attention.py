"""A prefill chunk's tiled latent attention (``ops/latent_chunk_attention``)
against ``model.latent_attention(absorbed=False)``, the einsum it replaces
on the chip, in float32 and interpreted on the CPU at small shapes: 32 heads
(ling-3.0-flash-ep8) and 64 (longcat-flash-omni-ep32), queries at unit scale
and at a q-LoRA's (``sqrt(hidden / q_lora_rank)`` = 2 there).

The cases are the places a walk can go wrong: a chunk that starts at 0, one
that starts mid-block behind a prefix hit (``start`` no multiple of the
tile), a context that ends on a tile's edge and one key past it, a row
padded behind its valid prefix, a q tile of pads alone, and a table wider
than the context.  Every key past a row's context holds NaN, so one that
reaches the result shows.

``python -m tests.test_latent_chunk_attention`` runs the cells' own shapes
(T = 512, S = 2048 and 4096, bfloat16, tiles as ``chunk_tiles`` gives them)
on whatever device JAX has (on the chip: compiled) against the einsum on
the same device and both against the einsum in float32, one JSON line a
case, exit 1 if the tiled form is further from the float32 result than the
bfloat16 einsum is (by a quarter in rms, by twice at the worst element)."""

import json
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.observability.stepstats import latent_keys_walked
from dynamo_tpu.ops.latent_chunk_attention import (
    chunk_tiles, latent_chunk_attention,
)

R, DN, DR, DV, LW = 64, 32, 16, 32, 128      # a latent page in miniature
TILES = (32, 128)                            # (q_tile, kv_tile) of the cases

# name: (start, length, T, S)
CASES = {
    "from_zero": (0, 64, 64, 256),
    "mid_block_after_a_hit": (203, 64, 64, 512),
    "ends_on_a_tile_edge": (192, 64, 64, 256),
    "one_key_past_the_edge": (193, 64, 64, 512),
    "padded_row": (100, 41, 64, 256),
    "a_q_tile_of_pads": (37, 20, 64, 256),
    "table_wider_than_context": (5, 32, 32, 1024),
}


def _cfg(r=R, dn=DN, dr=DR, dv=DV):
    return types.SimpleNamespace(kv_lora_rank=r, qk_nope_head_dim=dn,
                                 qk_rope_head_dim=dr, v_head_dim=dv)


def make_case(seed, start, length, T, S, H, q_scale, *, dims=(R, DN, DR, DV, LW),
              dtype=jnp.float32):
    r, dn, dr, dv, lw = dims
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    positions = np.full((1, T), -1, np.int32)
    positions[0, :length] = start + np.arange(length)
    ctx = normal(1, S, lw)
    ctx[:, start + length:] = np.nan         # nothing there may be read
    cast = lambda a: jnp.asarray(a, dtype)
    return dict(
        q_nope=cast(q_scale * normal(1, T, H, dn)),
        q_pe=cast(q_scale * normal(1, T, H, dr)), ctx=cast(ctx),
        wukv=cast(normal(r, H * (dn + dv)) / np.sqrt(r)),
        positions=jnp.asarray(positions))


def tiled(case, cfg, tiles, interpret):
    return latent_chunk_attention(
        case["q_nope"], case["q_pe"], case["ctx"], case["wukv"],
        case["positions"], rank=cfg.kv_lora_rank, rope=cfg.qk_rope_head_dim,
        scale=float((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5),
        tiles=tiles, interpret=interpret)


def einsum(case, cfg, length):
    """The oracle, on the keys that exist: the einsum multiplies the masked
    weights, exact zeros, with every key's value, so a NaN behind the
    context would reach its result; the tiled form must not need this."""
    ctx = jnp.where(jnp.arange(case["ctx"].shape[1])[None, :, None]
                    <= jnp.max(case["positions"]), case["ctx"], 0)
    return M.latent_attention(cfg, {"mla_wukv": case["wukv"]},
                              case["q_nope"], case["q_pe"], ctx,
                              case["positions"], absorbed=False)[:, :length]


@pytest.mark.parametrize("q_scale", [1.0, 2.0], ids=["plain", "q_lora"])
@pytest.mark.parametrize("H", [32, 64])
@pytest.mark.parametrize("name", list(CASES))
def test_the_tiled_form_is_the_einsum(name, H, q_scale):
    start, length, T, S = CASES[name]
    cfg = _cfg()
    case = make_case(list(CASES).index(name) + H, start, length, T, S, H, q_scale)
    got = np.asarray(tiled(case, cfg, TILES, True))
    assert np.isfinite(got).all()            # pads and NaN keys included
    want = np.asarray(einsum(case, cfg, length))
    np.testing.assert_allclose(got[:, :length], want, rtol=2e-5, atol=2e-5)
    if name == "a_q_tile_of_pads":
        assert not got[:, TILES[0]:].any()   # walked nothing: exact zeros


def test_one_q_tile_over_many_key_tiles_and_a_batch():
    """The cells' form (one q tile a chunk) and two rows of different
    contexts in one launch: each walks its own."""
    cfg = _cfg()
    a = make_case(1, 300, 64, 64, 512, 32, 1.0)
    b = make_case(2, 0, 17, 64, 512, 32, 1.0)
    both = {k: jnp.concatenate([a[k], b[k]]) if k != "wukv" else a[k]
            for k in a}
    got = np.asarray(tiled(both, cfg, (64, 128), True))
    for row, (case, n) in enumerate([(a, 64), (b, 17)]):
        case = {**case, "wukv": a["wukv"]}
        np.testing.assert_allclose(
            got[row:row + 1, :n], np.asarray(einsum(case, cfg, n)),
            rtol=2e-5, atol=2e-5)


def test_the_tiles_and_the_walk_as_the_host_counts_them():
    assert chunk_tiles(512, 2048) == (512, 512)
    assert chunk_tiles(16, 4096) == (16, 512)
    assert chunk_tiles(128, 128) == (128, 128)
    assert chunk_tiles(1024, 4096) == (512, 512)
    # a spec window, a table narrower than a lane tile: the einsum's
    assert chunk_tiles(5, 2048) is None
    assert chunk_tiles(16, 64) is None
    assert chunk_tiles(64, 1600) is None
    # one q tile: whole key tiles up to the chunk's last position
    one = (512, 512)
    assert latent_keys_walked(0, 512, one, 2048) == 512
    assert latent_keys_walked(1024, 512, one, 2048) == 1536
    assert latent_keys_walked(1025, 385, one, 2048) == 1536
    assert latent_keys_walked(1025, 512, one, 2048) == 2048
    assert latent_keys_walked(3000, 16, (16, 512), 4096) == 3072
    # q tiles of 512 in a longer chunk: each walks to its own diagonal
    assert latent_keys_walked(0, 1024, one, 4096) == 512 + 1024
    assert latent_keys_walked(0, 600, one, 4096) == 512 + 1024
    assert latent_keys_walked(7, 5, None, 2048) == 2048     # the einsum: all


# ------------------------------ on the chip --------------------------------

REAL = (512, 128, 64, 128, 640)              # r, dn, dr, dv, latent width


def _errors(got, truth):
    """(rms, worst element) of the difference over the truth's rms."""
    rms = np.sqrt(np.mean(truth ** 2))
    d = got - truth
    return float(np.sqrt(np.mean(d ** 2)) / rms), float(np.abs(d).max() / rms)


def main() -> int:
    """Both forms in bfloat16 against the einsum in float32 (``highest``)
    on the same bfloat16 inputs.  The first rows of a chunk that starts at 0
    attend a few keys, so their outputs are many times the rms and ONE
    bfloat16 step of the result there is 5-10 % of it: the worst element
    says how the result was rounded, so the tiled form is held to the
    einsum's own distance from the truth, not to the einsum."""
    cfg = _cfg(*REAL[:4])
    bad = 0
    for H, q_scale in ((32, 1.0), (64, 2.0)):
        for start, length, T, S in ((0, 512, 512, 512), (1536, 512, 512, 2048),
                                    (1029, 385, 512, 2048),
                                    (3584, 512, 512, 4096),
                                    (2000, 16, 16, 4096), (700, 100, 128, 1024)):
            case = make_case(start + H, start, length, T, S, H, q_scale,
                             dims=REAL, dtype=jnp.bfloat16)
            tiles = chunk_tiles(T, S)
            got = np.asarray(tiled(case, cfg, tiles, False), np.float32)
            bf16 = np.asarray(einsum(case, cfg, length), np.float32)
            with jax.default_matmul_precision("highest"):
                truth = np.asarray(einsum(
                    {k: v.astype(jnp.float32) if v.dtype == jnp.bfloat16
                     else v for k, v in case.items()}, cfg, length))
            rms, worst = _errors(got[:, :length], truth)
            rms_e, worst_e = _errors(bf16, truth)
            ok = bool(np.isfinite(got).all() and rms <= 1.25 * rms_e
                      and worst <= 2.0 * worst_e)
            bad += not ok
            print(json.dumps({
                "device": jax.devices()[0].device_kind, "heads": H,
                "q_scale": q_scale, "start": start, "length": length, "T": T,
                "S": S, "tiles": tiles, "rms_rel": round(rms, 5),
                "einsum_rms_rel": round(rms_e, 5), "worst": round(worst, 4),
                "einsum_worst": round(worst_e, 4), "ok": ok}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
