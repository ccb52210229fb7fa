"""Startup kernel auto-selection and per-shape-class tile autotuning.

BENCH_r05 measured the Pallas paged-attention decode kernel *losing* to the
XLA gathered-einsum path on real hardware (kernel_speedup 0.91) — which
path wins depends on generation/shape, so "auto" times both on the live
backend at engine startup and picks the winner.  The ragged kernel serves
three distinct shape classes (decode rows, spec ``[B, k+1]`` verify
windows, prefill chunks) whose arithmetic intensity differs wildly, so each
class is probed separately and gets its own ``attention_impl_{class}``
choice.  The probe is one small attention call per (impl, class) — tens of
ms total, not a model forward.

On top of the impl choice, this module sweeps the ragged kernel's
``(q_tile, kv_tile)`` tile space per shape class (ROADMAP item 2 — the
*Ragged Paged Attention* paper's win is exactly this per-shape grid
tuning).  Every candidate must pass a parity gate before it is eligible to
win: on CPU the sweep harness runs each candidate in Pallas interpret mode
and compares against an order-exact reference bit-for-bit (see
``reference_ragged``); at TPU runtime candidates are gated numerically
against the gathered-einsum path.  Winners are persisted in a JSON cache
(``DYNTPU_AUTOTUNE_CACHE``) keyed by a hash of (ModelConfig, EngineConfig
shape fields, device_kind, jax version) so startup pays the sweep once per
configuration and bench/serving share winners; a config drift changes the
key and falls back to defaults instead of replaying stale winners.

On non-TPU backends the choice is einsum without probing: Pallas only runs
in interpret mode there, which is orders of magnitude slower and would both
waste startup time and always lose anyway.

Run ``python -m dynamo_tpu.engine.autotune`` (CPU, with
``XLA_FLAGS=--xla_disable_hlo_passes=fusion``) to print the JSON parity
report the ``tune`` test suite asserts on.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils.logging import get_logger
from .config import EngineConfig, ModelConfig

log = get_logger("autotune")

# persisted sweep-winner cache path ("" / unset = no persistence)
CACHE_ENV = "DYNTPU_AUTOTUNE_CACHE"
# set to 0 to skip the startup tile sweep (impl probe still runs)
SWEEP_ENV = "DYNTPU_AUTOTUNE_SWEEP"
CACHE_VERSION = 2   # 2: kv_tile counts whole pages a step (PR 25)

# minimum second-to-minor tile dim per dtype (pallas_guide.md): kv_tile is
# the second-to-last axis of the (1, KV, kv_tile, hd) K/V block.  Quantized
# paged caches store 1-byte elements, whose native tile is (32, 128).
_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32, "fp8": 32}
# a swept tile replaces the kernel default only when it is this much faster
_SWEEP_MARGIN = 0.03


def _time_attention(fn, args, iters: int = 20) -> float:
    fn(*args).block_until_ready()  # warm (compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3


# ---------------------------------------------------------------------------
# impl microprobe (pallas vs einsum per shape class)
# ---------------------------------------------------------------------------


def _probe_class(
    model_config: ModelConfig, engine_config: EngineConfig,
    B: int, T: int,
) -> dict:
    """Time ragged-Pallas vs gathered-einsum on a ``[B, T]`` chunk shape.

    Rows attend a full ``W * block_size`` context (the chunk is its last
    ``T`` tokens) — the worst case for the einsum path's gathered scores
    and the steady state for the kernel's block streaming.
    """
    import jax
    import jax.numpy as jnp

    from ..ops.paged_attention import (
        paged_attention_decode, paged_attention_ragged,
    )
    from . import model as model_lib

    bs = engine_config.block_size
    W = max(2, min(8, engine_config.max_blocks_per_seq))
    KV = model_config.num_kv_heads
    H = model_config.num_heads
    hd = model_config.head_dim_
    NB = 1 + B * W
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if model_config.dtype == "bfloat16" else jnp.float32
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), dt)
    k = jnp.asarray(rng.standard_normal((NB, KV, bs, hd)), dt)
    v = jnp.asarray(rng.standard_normal((NB, KV, bs, hd)), dt)
    tables = jnp.asarray(1 + np.arange(B * W).reshape(B, W), jnp.int32)
    lens = jnp.full((B,), W * bs, jnp.int32)

    if T == 1:
        kernel = jax.jit(functools.partial(
            paged_attention_decode, block_size=bs))

        def pallas_path(q, kc, vc, tables, lens):
            return kernel(q[:, 0], kc, vc, tables, lens)[:, None]
    else:
        q_start = jnp.arange(B + 1, dtype=jnp.int32) * T
        q_lens = jnp.full((B,), T, jnp.int32)
        kernel = jax.jit(functools.partial(
            paged_attention_ragged, block_size=bs, max_q_len=T))

        def pallas_path(q, kc, vc, tables, lens):
            out = kernel(q.reshape(B * T, H, hd), kc, vc, tables,
                         q_start, q_lens, lens)
            return out.reshape(B, T, H, hd)

    @jax.jit
    def einsum_path(q, kc, vc, tables, lens):
        k_all = jnp.take(kc, tables.reshape(-1), axis=0).reshape(
            B, W, KV, bs, hd
        ).transpose(0, 1, 3, 2, 4).reshape(B, W * bs, KV, hd)
        v_all = jnp.take(vc, tables.reshape(-1), axis=0).reshape(
            B, W, KV, bs, hd
        ).transpose(0, 1, 3, 2, 4).reshape(B, W * bs, KV, hd)
        pos = (lens[:, None] - T) + jnp.arange(T)[None, :]
        return model_lib._attention(q, k_all, v_all, pos)

    args = (q, k, v, tables, lens)
    pallas_ms = _time_attention(jax.jit(pallas_path), args)
    einsum_ms = _time_attention(einsum_path, args)
    return {
        "impl": "pallas" if pallas_ms < einsum_ms else "einsum",
        "B": B, "T": T,
        "pallas_ms": round(pallas_ms, 4),
        "einsum_ms": round(einsum_ms, 4),
        # >1 means the Pallas kernel is faster
        "ratio": round(einsum_ms / max(pallas_ms, 1e-9), 3),
    }


def probe_attention_impl(
    model_config: ModelConfig, engine_config: EngineConfig,
) -> Tuple[EngineConfig, dict]:
    """Resolve ``attention_impl="auto"`` → concrete per-class impls.

    Returns (engine_config with the winners substituted — ``attention_impl``
    carries the decode winner for back-compat and each
    ``attention_impl_{decode,spec,prefill}`` its class winner — plus a
    choice-info dict with the per-class times and ratios under "classes").
    A probe that fails on the TPU raises.
    """
    import jax

    if engine_config.attention_impl != "auto":
        return engine_config, {
            "impl": engine_config.attention_impl, "probed": False,
        }

    choice: dict = {"probed": False, "classes": {}}
    impls = {"decode": "einsum", "spec": "einsum", "prefill": "einsum"}
    if jax.default_backend() != "tpu":
        # interpret-mode Pallas is not a contender; don't burn startup time
        choice.update(impl="einsum", reason="non-tpu backend")
    else:
        B_dec = min(16, max(engine_config.decode_buckets))
        shapes = {"decode": (B_dec, 1)}
        if engine_config.spec_mode != "off":
            shapes["spec"] = (B_dec, engine_config.spec_k + 1)
        shapes["prefill"] = (4, min(256, max(engine_config.prefill_buckets)))
        for cls, (B, T) in shapes.items():
            # a probe that cannot compile or run raises: "auto" chooses
            # between two working paths by time, it does not hide a
            # compiler error behind the other path
            info = _probe_class(model_config, engine_config, B, T)
            impls[cls] = info["impl"]
            choice["classes"][cls] = info
            choice["probed"] = True
        choice["impl"] = impls["decode"]
        # legacy top-level fields mirror the decode class (bench back-compat)
        dec = choice["classes"].get("decode", {})
        for key in ("pallas_ms", "einsum_ms", "ratio"):
            if key in dec:
                choice[key] = dec[key]
    log.info("attention_impl=auto resolved: %s", choice)
    resolved = dataclasses.replace(
        engine_config,
        attention_impl=choice.get("impl", "einsum"),
        attention_impl_decode=impls["decode"],
        attention_impl_spec=impls["spec"],
        attention_impl_prefill=impls["prefill"],
    )
    return resolved, choice


# ---------------------------------------------------------------------------
# tile sweep: candidate grid, parity gate, timing
# ---------------------------------------------------------------------------


def _sublane(dtype: str) -> int:
    return _SUBLANE.get(dtype, 8)


def class_shapes(
    model_config: ModelConfig, engine_config: EngineConfig,
) -> Dict[str, Tuple[int, int]]:
    """Representative ``(B, T)`` per shape class (the probe/sweep shapes)."""
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
    """The ``(q_tile, kv_tile)`` grid swept for one shape class.

    ``(0, 0)`` — the kernel default — is always first and always eligible,
    so the sweep can only ever match or beat the default.  q_tile must
    divide the class's query window T (decode: always 1).  kv_tile, the
    key positions one step of a row's KV walk covers, is offered at half
    and at twice the default's pages per step (``default_kv_tile`` of the
    shapes a launch sees: more pages a step amortise the step's fixed
    cost, fewer round a short context up less), and at the divisors of
    ``block_size`` that respect the dtype's minimum sublane tile (f32: 8,
    bf16: 16), since a sub-block tile is the second-to-minor axis of the
    page slice its DMA takes.
    """
    from ..ops.paged_attention import default_kv_tile
    from . import quant

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
    from . import quant

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
        import jax.numpy as jnp
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
    import jax
    import jax.numpy as jnp

    from ..ops.paged_attention import default_kv_tile

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
    import jax.numpy as jnp

    from ..ops.paged_attention import paged_attention_ragged
    from . import quant

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


def _ragged_scaled(q, kc, vc, tables, q_start, q_len, ctx_len,
                   k_scale, v_scale, **kw):
    """Positional-scales wrapper so the timing loop's ``fn(*args)`` shape
    works for both passthrough and quantized-KV candidates."""
    from ..ops.paged_attention import paged_attention_ragged

    return paged_attention_ragged(
        q, kc, vc, tables, q_start, q_len, ctx_len,
        k_scale=k_scale, v_scale=v_scale, **kw)


def _sweep_class_device(
    model_config: ModelConfig, engine_config: EngineConfig,
    attn_class: str, B: int, T: int,
) -> dict:
    """Time every candidate on the live backend; pick the fastest eligible.

    Eligibility at runtime is numeric — each candidate must match the
    naive reference within dtype tolerance on a clean (non-poisoned) mixed
    ragged case.  The table is as wide as the engine's own
    (``max_blocks_per_seq``) and candidates are timed at a context of a
    few hundred and of a few thousand tokens, scored on the sum: the
    sweep sees the cost it is tuning, and a winner can't overfit one
    depth.  A candidate replaces the default only when it beats it by
    more than ``_SWEEP_MARGIN``, so timing noise cannot flip the tile
    from one start to the next.
    """
    import jax
    import jax.numpy as jnp

    from ..ops.paged_attention import paged_attention_ragged
    from . import quant

    bs = engine_config.block_size
    cap = engine_config.max_blocks_per_seq
    depths = sorted({min(cap * bs, max(384, 2 * T)),
                     min(cap * bs, max(3072, 2 * T))})
    tol = 2e-2 if model_config.dtype == "bfloat16" else 2e-3
    if quant.is_quantized(engine_config.kv_dtype):
        tol = max(tol, 5e-2)  # quantization error rides the same anchor
    # each depth's case, and what every candidate is held to on it, once
    cases = []
    for depth in depths:
        case = make_sweep_case(
            model_config, engine_config, attn_class, B, T,
            W=cap, ctx=depth, poison=False)
        args_np = case["args"]
        ks_np, vs_np = case.get("k_scale"), case.get("v_scale")
        kc_h, vc_h = args_np[1], args_np[2]
        if ks_np is not None:
            kc_h = quant.kv_dequantize_cache_np(kc_h, ks_np)
            vc_h = quant.kv_dequantize_cache_np(vc_h, vs_np)
        ref = reference_naive(args_np[0], kc_h, vc_h, *args_np[3:],
                              block_size=bs)
        mask = valid_slot_mask(args_np[4], args_np[5], args_np[0].shape[0])
        args = tuple(jnp.asarray(a) for a in args_np)
        if ks_np is not None:
            args += (jnp.asarray(ks_np), jnp.asarray(vs_np))
        cases.append((depth, args, ref, mask))
    quantized = quant.is_quantized(engine_config.kv_dtype)
    results: List[dict] = []
    for q_tile, kv_tile in tile_candidates(
            model_config, engine_config, attn_class, T):
        entry = {"q_tile": q_tile, "kv_tile": kv_tile, "ms": {},
                 "eligible": True}
        total = 0.0
        # one throwaway wrapper per candidate BY DESIGN: each (q_tile,
        # kv_tile) is a distinct static config, so no cache is shared
        # and this cold startup sweep never runs in the serving loop
        fn = jax.jit(functools.partial(  # dynalint: disable=DT203
            _ragged_scaled if quantized else paged_attention_ragged,
            block_size=bs, max_q_len=T,
            q_tile=q_tile, kv_tile=kv_tile,
        ))
        for depth, args, ref, mask in cases:
            # no try: a tile the chip's compiler refuses is a kernel bug
            # to repair (or a candidate to take off the grid), not a
            # candidate to skip in silence
            out = np.asarray(fn(*args))
            err = float(np.max(np.abs(
                out.astype(np.float64)[mask] - ref[mask]), initial=0.0))
            if not np.isfinite(out.astype(np.float32)).all() \
                    or err > tol:
                entry["eligible"] = False
                entry["reason"] = f"numeric gate failed (err {err:.2e})"
                break
            ms = _time_attention(fn, args)
            entry["ms"][f"ctx{depth}"] = round(ms, 4)
            total += ms
        entry["total_ms"] = round(total, 4)
        results.append(entry)
    eligible = [e for e in results if e["eligible"]]
    if not eligible:
        raise RuntimeError(
            f"{attn_class}: no (q_tile, kv_tile) candidate passed the "
            f"numeric gate against the naive reference: {results!r}")
    winner = min(eligible, key=lambda e: e["total_ms"])
    default = results[0]
    if default["eligible"] and \
            winner["total_ms"] > (1.0 - _SWEEP_MARGIN) * default["total_ms"]:
        winner = default
    return {
        "B": B, "T": T, "width": cap, "depths": depths,
        "winner": (winner["q_tile"], winner["kv_tile"]),
        "candidates": results,
    }


# ---------------------------------------------------------------------------
# persisted tuning cache
# ---------------------------------------------------------------------------


def config_hash(
    model_config: ModelConfig, engine_config: EngineConfig,
    device_kind: str,
) -> str:
    """Cache key: shape-relevant config + device + jax version.

    Any drift in what the sweep actually measured — model geometry, cache
    layout, bucket grids, spec window, device generation, jax release —
    changes the key, so a stale winner can never be replayed; unknown keys
    fall back to kernel defaults.
    """
    import jax

    key = {
        "model": dataclasses.asdict(model_config),
        "engine": {
            "block_size": engine_config.block_size,
            "decode_buckets": list(engine_config.decode_buckets),
            "prefill_buckets": list(engine_config.prefill_buckets),
            "spec_mode": engine_config.spec_mode,
            "spec_k": engine_config.spec_k,
            "max_model_len": engine_config.max_model_len,
            "max_num_seqs": engine_config.max_num_seqs,
            "mesh_shape": list(engine_config.mesh_shape),
            # storage dtype changes the K/V DMA tile economics, so quant
            # winners never leak into bf16 runs (or vice versa)
            "kv_dtype": engine_config.kv_dtype,
        },
        "device_kind": device_kind,
        "jax": jax.__version__,
        "cache_version": CACHE_VERSION,
    }
    blob = json.dumps(key, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def cache_path() -> Optional[str]:
    return os.environ.get(CACHE_ENV) or None


def load_cache_entry(path: str, key: str) -> Optional[dict]:
    """The persisted entry for ``key``, or None on miss/drift/corruption."""
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("version") != CACHE_VERSION:
            return None
        entry = doc.get("entries", {}).get(key)
        if not isinstance(entry, dict) or "tiles" not in entry:
            return None
        return entry
    except (OSError, ValueError):
        return None


def store_cache_entry(path: str, key: str, entry: dict) -> bool:
    """Merge ``entry`` under ``key``; atomic rename, best-effort."""
    doc: dict = {"version": CACHE_VERSION, "entries": {}}
    try:
        with open(path) as f:
            old = json.load(f)
        if old.get("version") == CACHE_VERSION:
            doc = old
    except (OSError, ValueError):
        pass
    doc.setdefault("entries", {})[key] = entry
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return True
    except OSError as e:
        log.warning("autotune cache write failed (%s): %s", path, e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


# ---------------------------------------------------------------------------
# top-level: impl probe + tile resolution (what the engine calls)
# ---------------------------------------------------------------------------


def autotune_attention(
    model_config: ModelConfig, engine_config: EngineConfig,
) -> Tuple[EngineConfig, dict]:
    """Impl probe + per-class tile resolution, cache-backed.

    Order of precedence per class: explicit ``attention_tile_{class}`` in
    the config > persisted cache hit (``DYNTPU_AUTOTUNE_CACHE``) > on-TPU
    sweep (winners stored back) > kernel defaults.  The returned choice
    dict always carries ``autotune_cache_hit``, ``config_hash`` and the
    resolved ``tiles`` so bench/serving can report what actually ran.
    """
    import jax

    from ..utils.config import env_flag
    from . import model as model_lib

    cfg, choice = probe_attention_impl(model_config, engine_config)
    choice = dict(choice)
    device_kind = jax.devices()[0].device_kind
    key = config_hash(model_config, cfg, device_kind)
    path = cache_path()
    choice.update(autotune_cache_hit=False, config_hash=key,
                  cache_path=path or "")

    tiles: Dict[str, Tuple[int, int]] = {
        cls: (0, 0) for cls in ("decode", "spec", "prefill")}
    pallas_classes = [
        cls for cls in tiles
        if model_lib.resolve_attention_impl(cfg, cls) == "pallas"
    ]

    entry = load_cache_entry(path, key) if path else None
    if entry is not None:
        for cls, t in entry.get("tiles", {}).items():
            if cls in tiles and len(t) == 2:
                tiles[cls] = (int(t[0]), int(t[1]))
        choice["autotune_cache_hit"] = True
        choice["sweep"] = entry.get("sweep", {})
    elif (jax.default_backend() == "tpu" and pallas_classes
          and env_flag(SWEEP_ENV, True)):
        sweep: Dict[str, dict] = {}
        shapes = class_shapes(model_config, cfg)
        for cls in pallas_classes:
            B, T = shapes.get(cls, shapes["prefill"])
            res = _sweep_class_device(model_config, cfg, cls, B, T)
            tiles[cls] = tuple(res["winner"])
            sweep[cls] = res
        choice["sweep"] = sweep
        if path and sweep:
            store_cache_entry(path, key, {
                "device_kind": device_kind,
                "tiles": {cls: list(t) for cls, t in tiles.items()},
                "sweep": sweep,
            })

    # explicit config tiles always win over cache/sweep
    for cls in tiles:
        explicit = getattr(engine_config, f"attention_tile_{cls}")
        if tuple(explicit) != (0, 0):
            tiles[cls] = tuple(explicit)
    choice["tiles"] = {cls: list(t) for cls, t in tiles.items()}
    resolved = dataclasses.replace(
        cfg,
        attention_tile_decode=tiles["decode"],
        attention_tile_spec=tiles["spec"],
        attention_tile_prefill=tiles["prefill"],
    )
    return resolved, choice


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
