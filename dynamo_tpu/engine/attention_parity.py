"""The ragged paged-attention kernel's references and its parity gate.

Nothing here chooses anything: which implementation and which tile a step
program is traced with is ``engine.model.resolve_attention_impl`` and
``ops.paged_attention.default_kv_tile``.  This module holds what tests and
``chip_smoke.py`` hold the kernel to:

- ``reference_ragged``: an order-exact replay of the kernel's online-softmax
  recurrence for one ``(q_tile, kv_tile)``; an interpret-mode run must agree
  bit for bit,
- ``reference_naive``: a float64 softmax over the gathered context, the
  tile-independent anchor,
- ``make_sweep_case``: a mixed ragged batch (full, short, partial-q and dead
  rows) with NaN poison in every slot the kernel must never read,
- ``parity_check`` / ``sweep_class_parity`` / ``parity_selftest``: every tile
  of ``tile_candidates`` — the default and its neighbours, per shape class —
  through both references.

Run ``python -m dynamo_tpu.engine.attention_parity`` (CPU, with
``XLA_FLAGS=--xla_disable_hlo_passes=fusion``) to print the JSON parity
report the ``tune`` test suite asserts on.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import default_kv_tile, paged_attention_ragged
from . import quant
from .config import EngineConfig, ModelConfig

# minimum second-to-minor tile dim per dtype (pallas_guide.md): kv_tile is
# the second-to-last axis of the (1, KV, kv_tile, hd) K/V block.  Quantized
# paged caches store 1-byte elements, whose native tile is (32, 128).
_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32, "fp8": 32}


def _sublane(dtype: str) -> int:
    return _SUBLANE.get(dtype, 8)


def class_shapes(
    model_config: ModelConfig, engine_config: EngineConfig,
) -> Dict[str, Tuple[int, int]]:
    """Representative ``(B, T)`` per shape class (the parity gate's shapes)."""
    B_dec = min(16, max(engine_config.decode_buckets))
    shapes = {"decode": (B_dec, 1)}
    if engine_config.spec_mode != "off":
        shapes["spec"] = (B_dec, engine_config.spec_k + 1)
    shapes["prefill"] = (4, min(256, max(engine_config.prefill_buckets)))
    return shapes


def tile_candidates(
    model_config: ModelConfig, engine_config: EngineConfig,
    attn_class: str, T: int,
) -> List[Tuple[int, int]]:
    """The ``(q_tile, kv_tile)`` grid the parity gate walks for one shape
    class.

    ``(0, 0)`` — the kernel default, the one the engine runs — is always
    first.  q_tile must divide the class's query window T (decode: always
    1).  kv_tile, the key positions one step of a row's KV walk covers, is
    offered at half and at twice the default's pages per step
    (``default_kv_tile`` of the shapes a launch sees), and at the divisors
    of ``block_size`` that respect the dtype's minimum sublane tile (f32:
    8, bf16: 16), since a sub-block tile is the second-to-minor axis of the
    page slice its DMA takes.
    """
    bs = engine_config.block_size
    # the K/V page DMA's sublane floor follows the *storage* dtype: the
    # model dtype for bf16 passthrough, the 1-byte tile for quantized KV
    quantized = quant.is_quantized(engine_config.kv_dtype)
    page_dtype = engine_config.kv_dtype if quantized else model_config.dtype
    sub = _sublane(page_dtype)
    tp = engine_config.mesh_shape[-1]
    default = default_kv_tile(
        bs, max(1, model_config.num_kv_heads // tp), model_config.head_dim_,
        quant.np_storage_dtype(page_dtype) if quantized else page_dtype)
    kv_tiles = [0] + [
        kt for kt in (8, 16, 32, 64, 128)
        if kt >= sub and kt < bs and bs % kt == 0
    ] + [
        kt for kt in (default // 2, default * 2)
        if kt >= bs and kt % bs == 0 and kt != default
    ]
    if attn_class == "decode":
        q_tiles = [0]
    else:
        default_qt = min(T, 128) if T % min(T, 128) == 0 else T
        q_tiles = [0] + [
            qt for qt in (1, 2, 4, 8, 16, 32, 64, 128)
            if qt != default_qt and qt < T and T % qt == 0
        ]
    return [(qt, kt) for qt in q_tiles for kt in kv_tiles]


def make_sweep_case(
    model_config: ModelConfig, engine_config: EngineConfig,
    attn_class: str, B: int, T: int, *,
    W: int = 0, ctx: int = 0, seed: int = 0, poison: bool = True,
) -> dict:
    """A mixed ragged batch for one shape class's parity/timing runs.

    Rows pack with stride T (the engine layout).  Occupancy is
    deliberately ragged: full rows, a short-context row, a partial-q row
    (spec/prefill), a dead seat whose table is all trash (block 0), and —
    with ``poison`` — NaN bits in the trash block and every partial block
    tail, so a tile candidate that mis-masks can never pass the gate.
    ``W`` is the table's width, ``ctx`` the context of the full rows
    (default: all of the table); columns past a context stay 0, the trash
    block.

    With a quantized ``engine_config.kv_dtype`` the caches are quantized
    per (slot, head) and the case carries the parallel ``k_scale`` /
    ``v_scale`` arrays; poisoning then NaNs the *scales* of trash/tail
    slots (and the fp8 payload, which can encode NaN) — a candidate that
    dequantizes a masked slot before zeroing it still fails the gate.
    """
    bs = engine_config.block_size
    W = W or max(2, min(8, engine_config.max_blocks_per_seq))
    KV = model_config.num_kv_heads
    H = model_config.num_heads
    hd = model_config.head_dim_
    rng = np.random.default_rng(seed)
    dt = np.dtype("float32") if model_config.dtype != "bfloat16" else None

    rows = []  # (q_len, ctx_len)
    full_ctx = max(ctx or W * bs, T + 3)
    if full_ctx > W * bs:
        raise ValueError(f"ctx {ctx} does not fit a table of {W} blocks")
    for b in range(B):
        mode = b % 4
        if mode == 0:
            rows.append((T, full_ctx))               # steady state
        elif mode == 1:
            rows.append((T, T + (bs // 2)))          # short ctx, partial tail
        elif mode == 2:
            rows.append((max(1, T // 2), full_ctx - 3))  # partial q window
        else:
            rows.append((0, 0))                      # dead seat / all trash
    nb = 1 + sum((cl + bs - 1) // bs for _, cl in rows)
    q = rng.standard_normal((B * T, H, hd)).astype(np.float32)
    k_cache = rng.standard_normal((nb, KV, bs, hd)).astype(np.float32)
    v_cache = rng.standard_normal((nb, KV, bs, hd)).astype(np.float32)
    tables = np.zeros((B, W), np.int32)
    nxt = 1
    poison_slots = []  # (block, first poisoned slot offset)
    for r, (ql, cl) in enumerate(rows):
        for w in range((cl + bs - 1) // bs):
            tables[r, w] = nxt
            nxt += 1
        if poison and cl % bs:
            poison_slots.append((int(tables[r, cl // bs]), cl % bs))
    if poison:
        poison_slots.append((0, 0))  # the trash block, wholesale

    kv_dtype = engine_config.kv_dtype
    quantized = quant.is_quantized(kv_dtype)
    k_scale = v_scale = None
    if quantized:
        # quantize the clean values first, then poison the quantized form
        k_cache, k_scale = quant.kv_quantize_cache_np(k_cache, kv_dtype)
        v_cache, v_scale = quant.kv_quantize_cache_np(v_cache, kv_dtype)
    for blk, off in poison_slots:
        if quantized:
            k_scale[blk, :, off:] = np.nan
            v_scale[blk, :, off:] = np.nan
            if kv_dtype == "fp8":  # e4m3fn encodes NaN; int8 cannot
                k_cache[blk, :, off:] = np.nan
                v_cache[blk, :, off:] = np.nan
        else:
            k_cache[blk, :, off:] = np.nan
            v_cache[blk, :, off:] = np.nan
    if dt is None:
        q = np.asarray(jnp.asarray(q, jnp.bfloat16))
        if not quantized:
            k_cache = np.asarray(jnp.asarray(k_cache, jnp.bfloat16))
            v_cache = np.asarray(jnp.asarray(v_cache, jnp.bfloat16))
    return {
        "attn_class": attn_class,
        "args": (
            q, k_cache, v_cache, tables,
            np.arange(B + 1, dtype=np.int32) * T,
            np.asarray([r[0] for r in rows], np.int32),
            np.asarray([r[1] for r in rows], np.int32),
        ),
        "k_scale": k_scale,
        "v_scale": v_scale,
        "kv_dtype": kv_dtype,
        "block_size": bs,
        "max_q_len": T,
    }


def reference_ragged(
    q, k_cache, v_cache, tables, q_start, q_len, ctx_len, *,
    block_size: int, max_q_len: int, q_tile: int = 0, kv_tile: int = 0,
    k_scale=None, v_scale=None,
) -> np.ndarray:
    """Order-exact reference for one ``(q_tile, kv_tile)`` candidate.

    Replays the kernel's per-(row, q-tile, kv-tile) online-softmax
    recurrence — one update per tile of ``kv_tile`` key positions, a tile
    of several pages gathered into one operand as the kernel's DMAs do,
    the walk ending at the q tile's causal frontier — with the same ops,
    shapes, and reduction order through plain jnp, so an interpret-mode
    run of the candidate must agree **bit-for-bit** (assert with
    ``np.array_equal``; run both under
    ``XLA_FLAGS=--xla_disable_hlo_passes=fusion`` so XLA cannot re-fuse
    one side differently).  Different tile configs produce different —
    individually exact — references: tiling changes the accumulation
    order, which is precisely what this pins down.  Use a naive softmax
    (``reference_naive``) as the everything-independent correctness
    anchor under tolerance.
    """
    Tq, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    R, W = tables.shape
    bs = block_size
    if q_tile <= 0:
        q_tile = min(max_q_len, 128) if max_q_len % min(max_q_len, 128) == 0 \
            else max_q_len
    if kv_tile <= 0:
        kv_tile = default_kv_tile(bs, KV, hd, np.asarray(k_cache).dtype)
    pieces, piece = max(1, kv_tile // bs), min(kv_tile, bs)
    scale = 1.0 / (hd ** 0.5)
    q4 = jnp.asarray(q).reshape(Tq, KV, G, hd).transpose(1, 0, 2, 3)
    kc = jnp.asarray(k_cache)
    vc = jnp.asarray(v_cache)
    ks = jnp.asarray(k_scale) if k_scale is not None else None
    vs = jnp.asarray(v_scale) if v_scale is not None else None
    out = np.zeros((KV, Tq, G, hd), np.asarray(q).dtype)
    for r in range(R):
        qs, qe = int(q_start[r]), int(q_start[r + 1])
        ql, cl = int(q_len[r]), int(ctx_len[r])
        for t in range((qe - qs) // q_tile):
            live = t * q_tile < ql
            last_q = min((t + 1) * q_tile, ql) - 1
            max_vis = cl - ql + last_q
            m = jnp.full((KV, q_tile * G, 1), -jnp.inf, jnp.float32)
            l = jnp.zeros((KV, q_tile * G, 1), jnp.float32)
            acc = jnp.zeros((KV, q_tile * G, hd), jnp.float32)
            for w in range(-(-(max_vis + 1) // kv_tile) if live else 0):
                qf = q4[:, qs + t * q_tile: qs + (t + 1) * q_tile]
                qf = qf.astype(jnp.float32).reshape(KV, q_tile * G, hd)
                kps, vps = [], []
                for j in range(pieces):
                    pos = w * kv_tile + j * piece
                    # a tile rounds the walk up: columns past the table
                    # reread its last entry (positions >= ctx_len, masked)
                    blk = int(tables[r, min(pos // bs, W - 1)])
                    sl = slice(pos % bs, pos % bs + piece)
                    kp = kc[blk][:, sl].astype(jnp.float32)
                    vp = vc[blk][:, sl].astype(jnp.float32)
                    if ks is not None:
                        # same op order as the kernel: dequantize, THEN
                        # the kvalid zeroing wipes trash/tail bits (NaN
                        # scales incl.)
                        kp = kp * ks[blk][:, sl].astype(
                            jnp.float32)[..., None]
                        vp = vp * vs[blk][:, sl].astype(
                            jnp.float32)[..., None]
                    kps.append(kp)
                    vps.append(vp)
                k = kps[0] if pieces == 1 else jnp.concatenate(kps, axis=1)
                v = vps[0] if pieces == 1 else jnp.concatenate(vps, axis=1)
                kpos = w * kv_tile + jax.lax.broadcasted_iota(
                    jnp.int32, (1, kv_tile, 1), 1)
                kvalid = kpos < cl
                k = jnp.where(kvalid, k, 0.0)
                v = jnp.where(kvalid, v, 0.0)
                s = jax.lax.dot_general(
                    qf, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                ) * scale
                qi = t * q_tile + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1) // G
                spos = w * kv_tile + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 2)
                s = jnp.where((qi < ql) & (spos <= cl - ql + qi), s,
                              -jnp.inf)
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m, m_cur)
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe,
                                          -jnp.inf))
                p = jnp.exp(s - m_safe)
                m = m_new
                l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + jax.lax.dot_general(
                    p, v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
            o = acc / jnp.where(l == 0.0, 1.0, l)
            out[:, qs + t * q_tile: qs + (t + 1) * q_tile] = np.asarray(
                o.reshape(KV, q_tile, G, hd).astype(q4.dtype))
    return out.transpose(1, 0, 2, 3).reshape(Tq, H, hd)


def reference_naive(
    q, k_cache, v_cache, tables, q_start, q_len, ctx_len, *,
    block_size: int,
) -> np.ndarray:
    """Naive numpy softmax over the gathered context (float64 accumulate).

    The tile-order-independent correctness anchor: every candidate must
    stay within tolerance of this, on top of the bitwise match against its
    own ``reference_ragged``.  NaN-poisoned cache slots are zeroed first —
    positions past ``ctx_len`` are masked anyway, the kernel contract says
    their bits never matter.
    """
    q = np.nan_to_num(np.asarray(q, np.float64))
    kc = np.nan_to_num(np.asarray(k_cache, np.float64))
    vc = np.nan_to_num(np.asarray(v_cache, np.float64))
    Tq, H, hd = q.shape
    KV = kc.shape[1]
    G = H // KV
    R, W = tables.shape
    bs = block_size
    scale = 1.0 / (hd ** 0.5)
    out = np.zeros((Tq, H, hd), np.float64)
    for r in range(R):
        qs = int(q_start[r])
        ql, cl = int(q_len[r]), int(ctx_len[r])
        if ql == 0:
            continue
        ctx_k = np.concatenate(
            [kc[tables[r, w]] for w in range((cl + bs - 1) // bs)] or
            [np.zeros((KV, 0, hd))], axis=1)[:, :cl]      # [KV, cl, hd]
        ctx_v = np.concatenate(
            [vc[tables[r, w]] for w in range((cl + bs - 1) // bs)] or
            [np.zeros((KV, 0, hd))], axis=1)[:, :cl]
        for i in range(ql):
            pos = cl - ql + i
            for h in range(H):
                kv = h // G
                s = ctx_k[kv, :pos + 1] @ q[qs + i, h] * scale
                p = np.exp(s - s.max())
                out[qs + i, h] = (p / p.sum()) @ ctx_v[kv, :pos + 1]
    return out


def valid_slot_mask(q_start, q_len, n_slots: int) -> np.ndarray:
    """True for the flat query slots that hold a real query — the only
    ones compared against ``reference_naive`` (slots past ``q_len`` are
    exact zeros by contract, the naive reference skips them)."""
    mask = np.zeros(n_slots, bool)
    for r in range(len(q_len)):
        mask[int(q_start[r]): int(q_start[r]) + int(q_len[r])] = True
    return mask


def parity_check(
    case: dict, q_tile: int, kv_tile: int, *, tol: float = 2e-3,
) -> dict:
    """Run one candidate in interpret mode and gate it against references.

    Returns ``{"bitwise": ..., "max_err_exact": ..., "max_err_naive": ...,
    "eligible": ...}``.  ``bitwise`` requires the fusion pass disabled
    (see ``reference_ragged``); ``eligible`` additionally demands the
    naive-softmax anchor within ``tol`` and a NaN-free output.
    """
    q, kc, vc, tables, q_start, q_len, ctx_len = case["args"]
    ks, vs = case.get("k_scale"), case.get("v_scale")
    out = np.asarray(paged_attention_ragged(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(q_start), jnp.asarray(q_len),
        jnp.asarray(ctx_len),
        block_size=case["block_size"], max_q_len=case["max_q_len"],
        q_tile=q_tile, kv_tile=kv_tile, interpret=True,
        k_scale=None if ks is None else jnp.asarray(ks),
        v_scale=None if vs is None else jnp.asarray(vs),
    ))
    exact = reference_ragged(
        q, kc, vc, tables, q_start, q_len, ctx_len,
        block_size=case["block_size"], max_q_len=case["max_q_len"],
        q_tile=q_tile, kv_tile=kv_tile, k_scale=ks, v_scale=vs,
    )
    if ks is not None:
        # anchor on the dequantized caches: quantization error is shared
        # by kernel and anchor, leaving only accumulation-order noise
        kc = quant.kv_dequantize_cache_np(kc, ks)
        vc = quant.kv_dequantize_cache_np(vc, vs)
    naive = reference_naive(
        q, kc, vc, tables, q_start, q_len, ctx_len,
        block_size=case["block_size"],
    )
    finite = bool(np.isfinite(out.astype(np.float32)).all())
    bitwise = bool(np.array_equal(out, exact))
    err_exact = float(np.max(np.abs(
        out.astype(np.float64) - exact.astype(np.float64)), initial=0.0))
    mask = valid_slot_mask(q_start, q_len, out.shape[0])
    err_naive = float(np.max(np.abs(
        out.astype(np.float64)[mask] - naive[mask]), initial=0.0))
    return {
        "q_tile": q_tile, "kv_tile": kv_tile,
        "bitwise": bitwise, "finite": finite,
        "max_err_exact": err_exact, "max_err_naive": err_naive,
        "eligible": bool(bitwise and finite and err_naive <= tol),
    }


def sweep_class_parity(
    model_config: ModelConfig, engine_config: EngineConfig,
    attn_class: str, *, B: int = 0, T: int = 0, seed: int = 0,
) -> List[dict]:
    """CPU parity sweep: every candidate of one class through the gate."""
    shapes = class_shapes(model_config, engine_config)
    B0, T0 = shapes.get(attn_class, shapes["prefill"])
    B, T = B or B0, T or T0
    case = make_sweep_case(
        model_config, engine_config, attn_class, B, T, seed=seed)
    return [
        parity_check(case, qt, kt)
        for qt, kt in tile_candidates(
            model_config, engine_config, attn_class, T)
    ]


# ---------------------------------------------------------------------------
# CPU parity selftest (scripts/verify.sh tune drives this in a subprocess
# with XLA_FLAGS=--xla_disable_hlo_passes=fusion, see reference_ragged)
# ---------------------------------------------------------------------------


def parity_selftest(seed: int = 0, kv_dtype: str = "bf16") -> dict:
    """Every candidate of every class through the bitwise gate on CPU."""
    model_config = ModelConfig.tiny()
    engine_config = EngineConfig(
        block_size=16, num_blocks=128, max_num_seqs=8,
        max_num_batched_tokens=256, max_model_len=256,
        decode_buckets=(8,), prefill_buckets=(16, 32),
        spec_mode="ngram", spec_k=3, kv_dtype=kv_dtype,
    )
    report: dict = {
        "fusion_disabled": "--xla_disable_hlo_passes=fusion"
        in os.environ.get("XLA_FLAGS", ""),
        "classes": {}, "all_eligible": True,
    }
    for cls in ("decode", "spec", "prefill"):
        rows = sweep_class_parity(
            model_config, engine_config, cls, seed=seed)
        report["classes"][cls] = rows
        if not all(r["eligible"] for r in rows):
            report["all_eligible"] = False
    return report


if __name__ == "__main__":
    print(json.dumps(parity_selftest(), indent=1))
