"""Ring attention: sequence/context parallelism over an ICI mesh axis.

Long-context capability the reference lacks natively (SURVEY §2.3 marks
SP/CP/ring "absent" upstream — its lever is conditional disagg + chunked
prefill). Here the sequence is sharded over the ``sp`` mesh axis; each device
computes blockwise attention for its query chunk while K/V chunks rotate
around the ring via ``jax.lax.ppermute``, one hop per step, so:

- memory per device is O(T/sp) — T can exceed single-chip HBM;
- every hop is neighbor-to-neighbor over ICI (no all-gather of the sequence);
- compute overlaps communication: XLA schedules the next chunk's ppermute
  against the current chunk's attention FLOPs.

Softmax is accumulated online (flash-attention style m/l/acc in f32), so the
result is exact — identical to full attention over the unsharded sequence.

Layout contract: global ``q, k, v: [B, T, H, hd]`` sharded ``P(None, "sp")``
on the T axis; output identical. Causal masking uses absolute positions
derived from each chunk's ring position.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import layout


def _block_attend(q, k, v, qpos, kpos, m, l, acc, scale, causal):
    """One blockwise attention accumulation step (all f32).

    q: [B, Tq, H, hd]   k/v: [B, Tk, KV, hd]   qpos: [Tq]   kpos: [Tk]
    m, l: [B, Tq, H, 1]  acc: [B, Tq, H, hd]
    """
    H = q.shape[2]
    KV = k.shape[2]
    G = H // KV
    B, Tq = q.shape[0], q.shape[1]
    Tk = k.shape[1]
    hd = q.shape[3]

    qf = q.reshape(B, Tq, KV, G, hd)
    s = jnp.einsum("btkgh,bskh->btkgs", qf, k) * scale  # [B,Tq,KV,G,Tk]
    if causal:
        mask = kpos[None, :] <= qpos[:, None]           # [Tq, Tk]
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
    s = s.reshape(B, Tq, H, Tk)

    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    p = jnp.exp(s - m_safe)                             # [B,Tq,H,Tk]
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum(
        "btkgs,bskh->btkgh", p.reshape(B, Tq, KV, G, Tk), v
    ).reshape(B, Tq, H, hd)
    acc_new = acc * alpha + pv
    return m_new, l_new, acc_new


def ring_attention(
    q: jax.Array,      # [B, C, H, hd] local query chunk (C = T / sp)
    k: jax.Array,      # [B, C, KV, hd] local key chunk
    v: jax.Array,      # [B, C, KV, hd]
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Per-shard ring attention body — call inside ``shard_map``.

    Device i starts holding chunk i (positions [i*C, (i+1)*C)). At step s it
    attends over the chunk that started on device ``(i - s) mod n`` while
    sending its current chunk to neighbor ``i+1``.
    """
    n = jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    B, C, H, hd = q.shape
    scale = 1.0 / np.sqrt(hd)

    qf = q.astype(jnp.float32)
    qpos = i * C + jnp.arange(C)
    m = jnp.full((B, C, H, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, C, H, 1), jnp.float32)
    acc = jnp.zeros((B, C, H, hd), jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]
    cur_k, cur_v = k.astype(jnp.float32), v.astype(jnp.float32)
    for s in range(n):
        owner = (i - s) % n              # whose chunk we hold this step
        kpos = owner * C + jnp.arange(C)
        m, l, acc = _block_attend(
            qf, cur_k, cur_v, qpos, kpos, m, l, acc, scale, causal
        )
        if s != n - 1:  # final chunk needs no forwarding
            cur_k = jax.lax.ppermute(cur_k, axis_name, perm)
            cur_v = jax.lax.ppermute(cur_v, axis_name, perm)

    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh, axis: str = "sp", causal: bool = True
):
    """Jittable global-array ring attention: ``f(q, k, v) -> out`` with
    q/k/v ``[B, T, H|KV, hd]`` sharded over ``axis`` on T."""
    fn = functools.partial(ring_attention, axis_name=axis, causal=causal)
    spec = layout.spec(None, axis, None, None)
    return jax.jit(layout.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    ))
