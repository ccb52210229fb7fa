"""Quantized serving: int8/fp8 weights + quantized paged KV cache.

Storage convention (one scheme for both weights and KV, so every consumer
— jitted matmuls, the Pallas kernel, kvbm offload, the disagg wire — can
dequantize with a single multiply):

* **Weights**: a quantized leaf is a dict ``{"q": <storage dtype>,
  "s": float32}`` replacing the plain array in the param pytree. Scales
  are per-output-channel: amax is taken over the *input* (contraction)
  axis — axis ``-2`` for every matmul weight in this model family
  (``[L, in, out]`` stacked dense, ``[L, E, in, out]`` stacked experts,
  ``[D, V]`` lm_head) — with ``keepdims=True`` so ``q * s`` broadcasts
  back to the full-precision shape without reshapes. Norm weights, the
  embedding table, and MoE router weights stay in the model dtype: they
  are tiny and sit on the accuracy-critical path.

* **KV cache**: K/V pages store ``kv_dtype`` elements; scales live in
  parallel per-layer caches ``"ks"``/``"vs"`` of shape
  ``[num_blocks, KV, block_size]`` float32 — one scale per (slot, head).
  Per-token scales (rather than shared per-block) keep every byte-parity
  invariant the engine already pins: a token's quantized bytes depend
  only on that token's K/V, never on which block neighbours it landed
  next to, so spec-decode and chunked-prefill replays stay bit-exact.

``"bf16"`` means *unquantized passthrough*: params and cache keep the
model dtype and every code path compiles the exact pre-quant jaxpr — the
default config pays zero numerics tax.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp
import ml_dtypes
import numpy as np

# dtypes accepted by EngineConfig.weight_dtype / kv_dtype
QUANT_DTYPES = ("int8", "fp8")

# largest representable magnitude per storage dtype; amax maps onto it
QMAX = {"int8": 127.0, "fp8": 448.0}  # fp8 = e4m3fn

_JNP_STORAGE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
_NP_STORAGE = {
    "int8": np.dtype(np.int8),
    "fp8": np.dtype(ml_dtypes.float8_e4m3fn),
}


def is_quantized(dtype: str) -> bool:
    """True for the 1-byte storage modes, False for "bf16" passthrough."""
    return dtype in QUANT_DTYPES


def storage_dtype(dtype: str):
    """jnp storage dtype for a quantized mode."""
    return _JNP_STORAGE[dtype]


def np_storage_dtype(dtype: str) -> np.dtype:
    """numpy storage dtype (host staging / wire / kvbm tiers)."""
    return _NP_STORAGE[dtype]


# --------------------------- weight quantization ---------------------------

# matmul weights quantized at load time; everything else (norms, embed,
# w_router) stays in the model dtype
QUANTIZED_LEAVES = frozenset(
    ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"]
)


def is_weight_leaf(name: str) -> bool:
    return name in QUANTIZED_LEAVES


def quantize_np(w: np.ndarray, dtype: str) -> Dict[str, np.ndarray]:
    """Quantize one host-staged tensor: per-output-channel scales over
    the contraction axis (-2), ``keepdims`` so dequant is one multiply."""
    wf = np.asarray(w, np.float32)
    amax = np.max(np.abs(wf), axis=-2, keepdims=True)
    s = (amax / QMAX[dtype]).astype(np.float32)
    s[s == 0.0] = 1.0  # all-zero channels: keep q = 0 without 0/0
    q = wf / s
    if dtype == "int8":
        q = np.clip(np.rint(q), -127.0, 127.0)
    return {"q": q.astype(_NP_STORAGE[dtype]), "s": s}


def quantize_jnp(w: jnp.ndarray, dtype: str) -> Dict[str, jnp.ndarray]:
    """Device-side twin of :func:`quantize_np` (same rounding: rint)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    s = amax / QMAX[dtype]
    s = jnp.where(s == 0.0, 1.0, s).astype(jnp.float32)
    q = wf / s
    if dtype == "int8":
        q = jnp.clip(jnp.rint(q), -127.0, 127.0)
    return {"q": q.astype(_JNP_STORAGE[dtype]), "s": s}


def dequantize_np(leaf: Dict[str, np.ndarray],
                  dtype: str = "float32") -> np.ndarray:
    return (np.asarray(leaf["q"], np.float32) * leaf["s"]).astype(dtype)


def quantize_params(params: Dict[str, Any], weight_dtype: str
                    ) -> Dict[str, Any]:
    """Quantize a loaded (device or host) param tree in place-shape:
    matmul leaves become ``{"q", "s"}`` dicts; the rest pass through.
    Already-quantized trees (dict leaves) are returned unchanged so the
    engine can accept pre-quantized params from the streaming loader."""
    if not is_quantized(weight_dtype):
        return params
    out: Dict[str, Any] = {}
    for name, leaf in params.items():
        if name == "layers":
            out[name] = {
                k: (quantize_jnp(v, weight_dtype)
                    if is_weight_leaf(k) and not isinstance(v, dict) else v)
                for k, v in leaf.items()
            }
        elif is_weight_leaf(name) and not isinstance(leaf, dict):
            out[name] = quantize_jnp(leaf, weight_dtype)
        else:
            out[name] = leaf
    return out


# ----------------------------- KV quantization -----------------------------


def kv_quantize(x: jnp.ndarray, kv_dtype: str
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize freshly-projected K or V rows ``[N, KV, hd]`` to the
    storage dtype with one float32 scale per (token, head): returns
    ``(q [N, KV, hd], s [N, KV])``. Deterministic per token — the bytes
    never depend on block placement."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = amax / QMAX[kv_dtype]
    s = jnp.where(s == 0.0, 1.0, s).astype(jnp.float32)
    q = xf / s[..., None]
    if kv_dtype == "int8":
        q = jnp.clip(jnp.rint(q), -127.0, 127.0)
    return q.astype(_JNP_STORAGE[kv_dtype]), s


def kv_dequantize(q: jnp.ndarray, s: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Invert :func:`kv_quantize`: ``q`` [..., hd] times ``s`` [...]."""
    return (q.astype(jnp.float32) * s[..., None].astype(jnp.float32)
            ).astype(dtype)


def kv_quantize_cache_np(cache: np.ndarray, kv_dtype: str
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side twin of :func:`kv_quantize` over a whole paged cache
    ``[NB, KV, bs, hd]``: returns ``(q same-shape storage, s [NB, KV, bs]
    f32)``.  Used by test harnesses to build quantized fixtures."""
    xf = np.asarray(cache, np.float32)
    amax = np.max(np.abs(xf), axis=-1)
    s = (amax / QMAX[kv_dtype]).astype(np.float32)
    s[s == 0.0] = 1.0
    q = xf / s[..., None]
    if kv_dtype == "int8":
        q = np.clip(np.rint(q), -127.0, 127.0)
    return q.astype(_NP_STORAGE[kv_dtype]), s


def kv_dequantize_cache_np(q: np.ndarray, s: np.ndarray,
                           dtype=np.float32) -> np.ndarray:
    """Invert :func:`kv_quantize_cache_np`."""
    return (np.asarray(q, np.float32) * s[..., None]).astype(dtype)
