"""Ulysses-style sequence parallelism: all-to-all head scatter.

Alternative to ring attention (SURVEY §5 long-context): instead of rotating
K/V chunks, one ``all_to_all`` re-shards the tensors from sequence-sharded
``[B, T/sp, H, hd]`` to head-sharded ``[B, T, H/sp, hd]``, each device runs
*full-sequence* attention over its head group, and a second ``all_to_all``
restores sequence sharding. Two collectives total (vs sp-1 ppermute hops),
at the cost of requiring ``H % sp == 0`` and full-T activations per device
during attention. Better for moderate T / large sp; ring wins when T is the
memory bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import layout


def _full_attention(q, k, v, causal: bool):
    """Vanilla causal attention, f32 accumulation. q: [B, T, H, hd],
    k/v: [B, T, KV, hd] (GQA: H % KV == 0)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, T, KV, G, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("btkgh,bskh->btkgs", qf, kf) / np.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskh->btkgh", p, vf).reshape(B, T, H, hd)
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,   # [B, C, H, hd] sequence-sharded (C = T / sp)
    k: jax.Array,   # [B, C, KV, hd]
    v: jax.Array,   # [B, C, KV, hd]
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Per-shard Ulysses body — call inside ``shard_map``.

    Requires ``H % sp == 0`` and ``KV % sp == 0``.
    """
    n = jax.lax.axis_size(axis_name)
    B, C, H, hd = q.shape
    KV = k.shape[2]
    if H % n or KV % n:
        raise ValueError(f"heads ({H}, kv {KV}) must divide sp={n}")

    def seq_to_heads(x):
        # [B, C, Hx, hd] -> [B, n*C, Hx/n, hd]: split heads, all-to-all the
        # head groups against the sequence axis
        Hx = x.shape[2]
        x = x.reshape(B, C, n, Hx // n, hd)
        # concat_axis=1 (sequence), split_axis=2 (head groups)
        x = jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )
        return x.reshape(B, n * C, Hx // n, hd)

    def heads_to_seq(x, Hx):
        # [B, n*C, Hx/n, hd] -> [B, C, Hx, hd]: send sequence chunk j back
        # to device j, gather head groups
        x = x.reshape(B, n, C, Hx // n, hd)
        x = jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=3, tiled=True
        )                     # [B, 1, C, Hx, hd]
        return x.reshape(B, C, Hx, hd)

    qh = seq_to_heads(q)    # [B, T, H/n, hd]
    kh = seq_to_heads(k)    # [B, T, KV/n, hd]
    vh = seq_to_heads(v)
    out = _full_attention(qh, kh, vh, causal)
    return heads_to_seq(out, H)


def make_ulysses_attention(
    mesh: Mesh, axis: str = "sp", causal: bool = True
):
    """Jittable global-array Ulysses attention (same contract as
    ``make_ring_attention``)."""
    fn = functools.partial(ulysses_attention, axis_name=axis, causal=causal)
    spec = layout.spec(None, axis, None, None)
    return jax.jit(layout.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    ))
