"""Kimi Delta Attention (KDA, arXiv:2510.26692): the mathematics of a
linear-attention layer whose memory is one matrix a head, in plain XLA.

A head keeps a state ``S [dk, dv]`` in float32.  Token ``t`` brings a query
and a key (L2-normalised, the query times ``dk ** -0.5``), a value, a log
decay ``g_t [dk] <= 0`` a channel and a step size ``beta_t`` in (0, 1):

    S <- Diag(exp(g_t)) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T          (the delta rule)
    o_t = S^T q_t

:func:`kda_step` is that recurrence for one token a row (a decode step: it
reads and writes the whole state once), :func:`kda_scan` the same over ``T``
tokens one after the other (what the chunked form is tested against), and
:func:`kda_chunked` the published chunked form for a prefill chunk: the
sequence is cut into chunks of ``CHUNK`` tokens, inside a chunk the deltas
``u_t = v_t - S'^T k_t`` solve a unit lower-triangular system that does not
need the state token by token, and only ``T / CHUNK`` steps are sequential.

Inside a chunk, with ``G_t = g_1 + .. + g_t`` and ``S_0`` the state the
chunk starts from::

    (I + tril(A, -1) Diag(beta)) U = V - (K * exp(G)) S_0
        A[t, r] = sum_c k_t[c] k_r[c] exp(G_t[c] - G_r[c])
    o_t = S_0^T (q_t * exp(G_t)) + sum_{r <= t} B[t, r] beta_r u_r
        B[t, r] = sum_c q_t[c] k_r[c] exp(G_t[c] - G_r[c])
    S_C = Diag(exp(G_C)) S_0 + sum_r (k_r * exp(G_C - G_r)) beta_r u_r^T

``exp(G_t - G_r)`` is a decay a channel, so ``A`` and ``B`` are matrix
products only after it is split into a factor of ``t`` and one of ``r``, and
``exp(-G_r)`` alone overflows float32 after 18 tokens at the lower bound of
-5 a token.  The split is therefore made around a reference inside the
chunk: rows are taken ``SUB`` = 16 at a time, the reference of a block of
rows is ``G`` before its first token, a row's factor ``exp(G_t - ref)`` lies
in ``[e^-80, 1]`` and a column's ``exp(ref - G_r)`` in ``(0, e^80]``: both
inside float32.  That is what the bound on the decay is for
(``kda_lower_bound``, the "safe gate").

:func:`kda_step_seats` is :func:`kda_step` as a Pallas kernel over the seat
pool itself: the XLA form gathers the rows' states, passes over them three
times and scatters them back, 24.9 ms for 128 rows of six layers where the
bytes are 3.9 ms (PERF.md, PR 34); the kernel takes a row's ``[H, dk, dv]``
from its seat into on-chip memory once and puts it back once.

Pads inside a chunk are the caller's: a token with ``g = 0`` and ``beta =
0`` leaves the state as it was.  Everything is float32 at ``HIGHEST``
matmul precision: the state is multiplied by a decay at every token, and
what it is multiplied with is not rounded to bfloat16 on the way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16

_mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x`` over its norm along the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def short_conv(u: jax.Array, prev: jax.Array, w: jax.Array, n: jax.Array):
    """Causal depthwise convolution over time, ``K`` taps a channel.

    ``u [B, T, C]`` are the chunk's inputs, ``prev [B, K - 1, C]`` the
    ``K - 1`` inputs before it (zeros at the start of a sequence), ``w [K,
    C]`` the taps, oldest first: ``y_t = sum_j w[j] x_{t - (K - 1) + j}``.
    ``n [B]`` counts a row's valid tokens (a prefix of the row).  Returns
    ``y [B, T, C]`` in float32 and the ``K - 1`` inputs a next chunk starts
    from: those before position ``n``, so a row with no valid token hands
    back ``prev``."""
    K = w.shape[0]
    T = u.shape[1]
    full = jnp.concatenate([prev.astype(u.dtype), u], axis=1)   # [B, T+K-1, C]
    wf = w.astype(jnp.float32)
    y = sum(full[:, j:j + T].astype(jnp.float32) * wf[j] for j in range(K))
    nxt = jax.vmap(lambda r, s: jax.lax.dynamic_slice_in_dim(r, s, K - 1))(
        full, n)
    return y, nxt


def kda_step(S, q, k, v, g, beta):
    """One token a row: ``S [B, H, dk, dv]`` float32, ``q``, ``k``, ``g``
    ``[B, H, dk]``, ``v [B, H, dv]``, ``beta [B, H]``, all float32.
    Returns ``o [B, H, dv]`` and the new state.  Products and sums on the
    vector unit in float32: the step is bound by the state's bytes."""
    S = S * jnp.exp(g)[..., None]
    u = v - jnp.sum(S * k[..., None], axis=-2)
    S = S + (beta[..., None] * k)[..., None] * u[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def _step_kernel(seats_ref, fresh_ref, cols_ref, v_ref, s_ref, o_ref,
                 s_out_ref, *, heads: int):
    """One row: ``cols_ref [1, 4H, dk]`` holds the row's decay, key, step
    size times key and query a head (in that order, ``H`` rows each): the
    vectors that multiply the state along ``dk``.  One transpose makes them
    columns; ``v`` and the output run along ``dv`` and stay rows."""
    b = pl.program_id(0)
    cols = cols_ref[0].T                                    # [dk, 4H]
    fresh = fresh_ref[b] > 0
    for h in range(heads):
        col = lambda j: cols[:, j * heads + h][:, None]     # noqa: E731
        S = jnp.where(fresh, 0.0, s_ref[0, h]) * col(0)     # [dk, dv]
        u = v_ref[0, h][None, :] - jnp.sum(S * col(1), axis=0, keepdims=True)
        S = S + col(2) * u
        s_out_ref[0, h] = S
        o_ref[0, h] = jnp.sum(S * col(3), axis=0)


def kda_step_seats(pool, seats, fresh, q, k, v, g, beta, *,
                   interpret: bool = False):
    """:func:`kda_step` on the rows' seats of ``pool [S + 1, H, dk, dv]``
    float32, in place: row ``b`` reads and writes ``pool[seats[b]]`` (from
    zeros where ``fresh[b]``).  ``q``, ``k``, ``g`` ``[B, H, dk]``, ``v [B,
    H, dv]``, ``beta [B, H]``.  Returns ``o [B, H, dv]`` and the pool.

    Rows that share a seat (the trash seat's pad rows) must bring ``g = 0``
    and ``beta = 0``: each then writes back the bytes it read.  On the chip
    ``4 * H`` and ``dk`` fill whole 128 x 128 tiles (the one transpose a
    row); the interpreter takes any shape."""
    B, H, dk = k.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    cols = jnp.concatenate(
        [jnp.exp(g.astype(f32)), k.astype(f32),
         beta.astype(f32)[..., None] * k.astype(f32), q.astype(f32)],
        axis=1)                                             # [B, 4H, dk]
    row = lambda b, seats, fresh: (b, 0, 0)                 # noqa: E731
    seat = lambda b, seats, fresh: (seats[b], 0, 0, 0)      # noqa: E731
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, 4 * H, dk), row),
                      pl.BlockSpec((1, H, dv), row),
                      pl.BlockSpec((1, H, dk, dv), seat)],
            out_specs=[pl.BlockSpec((1, H, dv), row),
                       pl.BlockSpec((1, H, dk, dv), seat)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: seats, fresh, cols, v, pool -> the pool is output 1
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="kda_step",
    )(seats.astype(jnp.int32), fresh.astype(jnp.int32), cols,
      v.astype(f32), pool)
    return o, pool


def kda_scan(S, q, k, v, g, beta):
    """:func:`kda_step` over ``T`` tokens in turn: ``q``, ``k``, ``g`` ``[B,
    T, H, dk]``, ``v [B, T, H, dv]``, ``beta [B, T, H]``.  Returns ``o [B,
    T, H, dv]`` and the last state."""
    def body(S, x):
        o, S = kda_step(S, *x)
        return S, o

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(body, S, xs)
    return jnp.moveaxis(o, 0, 1), S


def _unit_lower_inverse(L: jax.Array) -> jax.Array:
    """``(I + L)^-1`` of strictly lower-triangular ``L [..., C, C]`` by
    forward substitution: row by row inside the ``SUB`` x ``SUB`` diagonal
    blocks (all blocks at once), then block by block.  Substitution and not
    a product of powers of ``L``: with keys that repeat the powers grow and
    cancel."""
    C = L.shape[-1]
    n = C // SUB
    blocks = L.reshape(L.shape[:-2] + (n, SUB, n, SUB))
    blk = lambda i, j: blocks[..., i, :, j, :]             # noqa: E731
    D = jnp.stack([blk(i, i) for i in range(n)], axis=-3)  # [.., n, SUB, SUB]
    eye = jnp.eye(SUB, dtype=L.dtype)
    rows = [jnp.broadcast_to(eye[0], D.shape[:-2] + (SUB,))]
    for t in range(1, SUB):
        done = jnp.stack(rows, axis=-2)                    # [.., t, SUB]
        rows.append(eye[t] - _mm("...r,...rc->...c", D[..., t, :t], done))
    X = jnp.stack(rows, axis=-2)                           # (I + D)^-1
    inv = [[None] * n for _ in range(n)]
    for i in range(n):
        inv[i][i] = X[..., i, :, :]
        for j in range(i):
            acc = sum(_mm("...ab,...bc->...ac", blk(i, p), inv[p][j])
                      for p in range(j, i))
            inv[i][j] = -_mm("...ab,...bc->...ac", inv[i][i], acc)
    zero = jnp.zeros_like(inv[0][0])
    return jnp.concatenate(
        [jnp.concatenate([inv[i][j] if j <= i else zero for j in range(n)],
                         axis=-1) for i in range(n)], axis=-2)


def kda_chunked(S, q, k, v, g, beta):
    """The chunked form over ``T`` tokens (shapes as :func:`kda_scan`; ``T``
    is padded to whole chunks here with tokens that change nothing).
    Returns ``o [B, T, H, dv]`` float32 and the state after the last
    token."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    C, nsb = CHUNK, CHUNK // SUB
    pad = -T % C
    q, k, v, g, beta = (
        jnp.pad(a.astype(jnp.float32),
                ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        for a in (q, k, v, g, beta))
    NC = (T + pad) // C

    def split(a):            # [B, T, H, ...] -> [B, H, NC, C, ...]
        a = a.reshape((B, NC, C) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                              # [B,H,NC,C,dk]
    ends = G.reshape(B, H, NC, nsb, SUB, dk)[..., -1, :]
    ref = jnp.concatenate(
        [jnp.zeros_like(ends[..., :1, :]), ends[..., :-1, :]], axis=-2)
    within = jnp.exp(G - jnp.repeat(ref, SUB, axis=-2))     # [e^-80, 1]
    # the columns as a block of rows sees them: exp(ref_I - G_r) up to the
    # block's own last column, nothing behind it
    col = jnp.arange(C)
    seen = col[None, :] < (jnp.arange(nsb)[:, None] + 1) * SUB   # [nsb, C]
    expo = jnp.where(seen[..., None],
                     ref[..., :, None, :] - G[..., None, :, :], 0.0)
    kd = jnp.where(seen[..., None], k[..., None, :, :] * jnp.exp(expo), 0.0)

    def against_columns(x):  # rows x [.., C, dk] -> [.., C, C]
        xb = (x * within).reshape(B, H, NC, nsb, SUB, dk)
        return _mm("...isd,...ird->...isr", xb, kd).reshape(B, H, NC, C, C)

    lower = col[:, None] > col[None, :]
    A = jnp.where(lower, against_columns(k), 0.0)
    Bm = jnp.where(lower | (col[:, None] == col[None, :]),
                   against_columns(q), 0.0)
    Tm = _unit_lower_inverse(A * beta[..., None, :])
    decay = jnp.exp(G)
    Wv = _mm("...tr,...rv->...tv", Tm, v)
    Wk = _mm("...tr,...rd->...td", Tm, k * decay)
    Gend = G[..., -1, :]                                    # [B,H,NC,dk]
    Kbar = k * jnp.exp(Gend[..., None, :] - G)

    def body(S, x):
        Wv_c, Wk_c, qt_c, Bm_c, beta_c, Kbar_c, dec_c = x
        U = (Wv_c - _mm("bhtd,bhdv->bhtv", Wk_c, S)) * beta_c[..., None]
        o = (_mm("bhtd,bhdv->bhtv", qt_c, S)
             + _mm("bhtr,bhrv->bhtv", Bm_c, U))
        S = dec_c[..., None] * S + _mm("bhtd,bhtv->bhdv", Kbar_c, U)
        return S, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in
               (Wv, Wk, q * decay, Bm, beta, Kbar, jnp.exp(Gend)))
    S, o = jax.lax.scan(body, S.astype(jnp.float32), xs)   # o [NC,B,H,C,dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        B, NC * C, H, dv)
    return o[:, :T], S
