"""Pallas TPU kernel: a prefill chunk's latent (MLA) attention, key tile by
key tile under a running softmax.

The chunk's queries ``[T, H, qk_nope | qk_rope]`` attend the gathered latents
``ctx [S, latent_width]`` (``c | k_pe | pad`` a token, positions ``0 .. S -
1``) in the EXPANDED form, ``k_nope | v = c Wukv`` a head, as
``engine.model.latent_attention(absorbed=False)`` does, with its numerics:
bfloat16 operands, float32 scores, float32 maximum / sum / accumulator, the
weights cast to bfloat16 for the product with ``v``, the scale ``(dn + dr)
** -0.5``.  What differs is where the work lives.  The einsum builds a
float32 ``[H, T, S]`` score array in memory, masks it, runs a softmax over
it and writes the bfloat16 weights beside it: eight passes over an array
nobody needs (0.8 GB a layer at T = 512, S = 2048, 64 heads), over the
table's padded width.  Here a ``[q_tile, kv_tile]`` block of one head's
scores lives on chip, and a row walks as far as its chunk's context.

The grid is ``(rows, head groups, q tiles, key tiles)``, the key tiles
innermost.  A grid step takes one key tile of the latents, multiplies it out
to each head's keys and values ON CHIP (``[kv_tile, r] @ [r, dn + dv]``:
with ``q_tile = T``, as wherever ``T <= 512``, once a head and key, what the
einsum's two expansions cost, and nothing of it goes to memory), adds the
rope part's scores against the ``k_pe`` all heads share, and folds the tile
into the running maximum, sum and accumulator (the flash form).  Two
scalars a (row, q tile) ride the scalar prefetch: the tile's last and first
valid position.  Key tiles past ``last`` are not visited: their grid steps
do nothing and their block index is clamped to the walk's last tile, so no
copy is started for them.  Tiles wholly at or below ``first`` need no
mask; only the tiles the diagonal crosses build one.

Keys beyond a q tile's last position (the rest of its last tile: a partial
block, a stale table tail, the trash block) hold arbitrary bits, NaN
included: their latents are zeroed before the MXU and their scores masked,
so they contribute exactly zero.  A pad row (position -1) sees no key and
returns finite numbers nobody reads; a q tile of pads alone walks nothing
and returns zeros.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_LANES = 128
_SUBLANES = 16            # a bfloat16 tile's rows: the least q tile
_Q_TILE = 512             # queries a grid step holds (a whole chunk)
_KV_TILE = 512            # keys a grid step expands and attends
_HEAD_GROUP = 2           # heads that share a grid step's key tile
_MASKED = -1e30           # a masked score (the einsum's, finite)


def chunk_tiles(T: int, S: int) -> Optional[Tuple[int, int]]:
    """``(q_tile, kv_tile)`` a ``[T]`` chunk over ``S`` gathered keys is
    walked with, or None where the shapes leave it to the einsum: a chunk
    that is not whole bfloat16 tiles of rows (a spec window), or a table
    whose width is not whole lane tiles of keys."""
    q_tile = min(T, _Q_TILE)
    kv_tile = min(S, _KV_TILE)
    if T % q_tile or q_tile % _SUBLANES or S % kv_tile or kv_tile % _LANES:
        return None
    return q_tile, kv_tile


def _walk(last, first, kv_tile):
    """Key tiles a q tile visits and how many of them need no mask."""
    walk = (last + kv_tile) // kv_tile          # cdiv(last + 1, kv_tile)
    return walk, jnp.minimum((first + 1) // kv_tile, walk)


def _chunk_kernel(
    # scalar prefetch
    last_ref,      # [B, nq] int32 a q tile's last valid position (-1: none)
    first_ref,     # [B, nq] int32 its first valid position
    # blocks
    pos_ref,       # [1, TQ, 1] int32 the queries' positions (-1: a pad)
    qn_ref,        # [1, TQ, G * dn]
    qp_ref,        # [1, G, TQ, dr]
    ctx_ref,       # [1, TK, latent_width]
    w_ref,         # [r, G * (dn + dv)]
    o_ref,         # [1, TQ, G * dv]
    # scratch
    m_ref,         # [G, TQ, 1] float32 running maximum
    l_ref,         # [G, TQ, 1] float32 running sum
    acc_ref,       # [G, TQ, dv] float32 running weighted values
    *, rank: int, rope: int, nope: int, vdim: int, scale: float,
):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    group = qp_ref.shape[1]
    kv_tile = ctx_ref.shape[1]
    last = last_ref[b, i]
    walk, full = _walk(last, first_ref[b, i], kv_tile)
    nt = (((1,), (1,)), ((), ()))               # a @ b.T
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked: bool):
        ctx = ctx_ref[0]
        c, k_pe = ctx[:, :rank], ctx[:, rank:rank + rope]
        if masked:
            # keys past the q tile's last position hold arbitrary bits:
            # zero them BEFORE the MXU (a masked score alone lets NaN * 0
            # through the product with v)
            kpos = j * kv_tile + jax.lax.broadcasted_iota(
                jnp.int32, (kv_tile, 1), 0)
            c = jnp.where(kpos <= last, c, jnp.zeros((), c.dtype))
            k_pe = jnp.where(kpos <= last, k_pe, jnp.zeros((), c.dtype))
            valid = (j * kv_tile + jax.lax.broadcasted_iota(
                jnp.int32, (1, kv_tile), 1)) <= pos_ref[0]   # [TQ, TK]
        for g in range(group):
            kv = jnp.dot(c, w_ref[:, g * (nope + vdim):(g + 1) * (nope + vdim)],
                         preferred_element_type=f32).astype(c.dtype)
            s = jax.lax.dot_general(
                qn_ref[0, :, g * nope:(g + 1) * nope], kv[:, :nope], nt,
                preferred_element_type=f32)
            s = (s + jax.lax.dot_general(
                qp_ref[0, g], k_pe, nt, preferred_element_type=f32)) * scale
            if masked:
                s = jnp.where(valid, s, _MASKED)
            m_prev = m_ref[g]                                # [TQ, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                           # [TQ, TK]
            m_ref[g] = m_new
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                p.astype(c.dtype), kv[:, nope:], preferred_element_type=f32)

    @pl.when(j < full)
    def _below_the_diagonal():
        tile(False)

    @pl.when((j >= full) & (j < walk))
    def _on_the_diagonal():
        tile(True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        for g in range(group):
            l = l_ref[g]
            o_ref[0, :, g * vdim:(g + 1) * vdim] = (
                acc_ref[g] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "rank", "rope", "scale", "tiles", "interpret"))
def latent_chunk_attention(q_nope: jax.Array, q_pe: jax.Array,
                           ctx: jax.Array, wukv: jax.Array,
                           positions: jax.Array, *, rank: int, rope: int,
                           scale: float, tiles: Tuple[int, int],
                           interpret: bool = False) -> jax.Array:
    """Causal attention of a chunk's ``q_nope [B, T, H, dn]`` / ``q_pe [B,
    T, H, dr]`` at ``positions [B, T]`` (-1: a pad; a row's valid tokens are
    a prefix) over the latents ``ctx [B, S, latent_width]`` of positions ``0
    .. S - 1``, expanded by ``wukv [rank, H * (dn + dv)]``; returns ``[B, T,
    H, dv]``.  ``tiles`` is ``chunk_tiles(T, S)``'s ``(q_tile, kv_tile)``.

    Jitted here, so that the layers of a step program, which are unrolled,
    share ONE lowered kernel (a Pallas call traced at every call site is
    lowered at every call site: PERF.md, PR 47)."""
    B, T, H, dn = q_nope.shape
    S = ctx.shape[1]
    dv = wukv.shape[1] // H - dn
    q_tile, kv_tile = tiles
    group = _HEAD_GROUP if H % _HEAD_GROUP == 0 else 1
    nq, nk = T // q_tile, S // kv_tile

    tiled = positions.reshape(B, nq, q_tile)
    last = jnp.max(tiled, axis=-1)
    first = jnp.min(jnp.where(tiled >= 0, tiled, S), axis=-1)

    def key_tile(b, h, i, j, last, first):
        # past the walk: the walk's last tile again, so nothing is copied
        return (b, jnp.minimum(j, jnp.maximum(last[b, i], 0) // kv_tile), 0)

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, rank=rank, rope=rope, nope=dn,
                          vdim=dv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // group, nq, nk),
            in_specs=[
                pl.BlockSpec((1, q_tile, 1),
                             lambda b, h, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, q_tile, group * dn),
                             lambda b, h, i, j, *_: (b, i, h)),
                pl.BlockSpec((1, group, q_tile, q_pe.shape[-1]),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, kv_tile, ctx.shape[-1]), key_tile),
                pl.BlockSpec((rank, group * (dn + dv)),
                             lambda b, h, i, j, *_: (0, h)),
            ],
            out_specs=pl.BlockSpec((1, q_tile, group * dv),
                                   lambda b, h, i, j, *_: (b, i, h)),
            scratch_shapes=[
                pltpu.VMEM((group, q_tile, 1), jnp.float32),
                pltpu.VMEM((group, q_tile, 1), jnp.float32),
                pltpu.VMEM((group, q_tile, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, H * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="latent_chunk_attention",
    )(last, first, positions[..., None], q_nope.reshape(B, T, H * dn),
      jnp.swapaxes(q_pe, 1, 2), ctx, wukv)
    return out.reshape(B, T, H, dv)
