"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): a linear-
attention layer whose memory is one ``[dk, dv]`` matrix a head and whose
decay is ONE number a head and token.

A head keeps a state ``S [dk, dv]`` in float32.  Token ``t`` brings a query
and a key (L2-normalised, the query times ``dk ** -0.5``), a value, a log
decay ``g_t <= 0`` and a step size ``beta_t`` in (0, 1), or in (0, 2) where
the layer allows negative eigenvalues (``I - beta k k^T`` then reaches -1)::

    S <- exp(g_t) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T          (the delta rule)
    o_t = S^T q_t

``ops/delta_rule.py`` has the same rule with a decay a channel (Kimi Delta
Attention) and this module's helpers (``short_conv``, ``l2norm``, the
inverse of a unit lower-triangular matrix by substitution).  With a scalar
decay the chunked form is simpler than KDA's, and that is why it is written
again here and not as KDA's with the gate broadcast: inside a chunk of
``CHUNK`` tokens, with ``G_t = g_1 + .. + g_t`` and ``S_0`` the state the
chunk starts from::

    D[t, r] = exp(G_t - G_r)  for t >= r, else 0       (in (0, 1]: g <= 0)
    A = (K K^T) * D,  strictly lower;   B = (Q K^T) * D,  lower
    (I + A Diag(beta)) U = V - exp(G) K S_0
    o_t = exp(G_t) S_0^T q_t + sum_{r <= t} B[t, r] beta_r u_r
    S_C = exp(G_C) S_0 + sum_r exp(G_C - G_r) beta_r k_r u_r^T

``D`` is a ``[C, C]`` matrix a head: no factor of it can overflow, so there
are no sub-blocks, no reference rows and no ``[C / 16, C, dk]`` expansion of
the keys, and no bound on the decay is needed.  :func:`gdn_step` is the
recurrence for one token a row, :func:`gdn_scan` the same over ``T`` tokens
in turn (what the chunked form is tested against), :func:`gdn_chunked` the
form above, and :func:`gdn_step_seats` the step as a Pallas kernel over the
seat pool itself.

**The pool's layout.**  A float32 plane ``[dk, dv]`` with ``dv`` no multiple
of 128 is padded to whole lane tiles in the chip's tiled memory (``[96,
192]``: 2.95 MB a row of 30 heads where 2.21 are counted).  So the pool
holds :func:`seat_group` heads side by side, ``[S + 1, H / hp, dk, hp *
dv]`` with ``hp`` the fewest heads whose values fill whole lane tiles (2 at
``dv`` 192: ``[15, 96, 384]``, 12 sublane tiles by 3 lane tiles a pair,
nothing padded; 1 at ``dv`` 128).  :func:`to_pool` / :func:`from_pool` turn
``[..., H, dk, dv]`` into that and back; the kernel works on a group's plane
as it lies, with the heads' columns selected by lane.

Pads inside a chunk are the caller's: a token with ``g = 0`` and ``beta =
0`` leaves the state as it was, to the bit.  Everything is float32 at
``HIGHEST`` matmul precision, as in ``delta_rule``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import _mm, _unit_lower_inverse, short_conv

CHUNK = 64
_LANES = 128


def gdn_step(S, q, k, v, g, beta):
    """One token a row: ``S [B, H, dk, dv]`` float32, ``q``, ``k`` ``[B, H,
    dk]``, ``v [B, H, dv]``, ``g``, ``beta`` ``[B, H]``, all float32.
    Returns ``o [B, H, dv]`` and the new state."""
    S = S * jnp.exp(g)[..., None, None]
    u = v - jnp.sum(S * k[..., None], axis=-2)
    S = S + (beta[..., None] * k)[..., None] * u[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def gdn_scan(S, q, k, v, g, beta):
    """:func:`gdn_step` over ``T`` tokens in turn: ``q``, ``k`` ``[B, T, H,
    dk]``, ``v [B, T, H, dv]``, ``g``, ``beta`` ``[B, T, H]``.  Returns ``o
    [B, T, H, dv]`` and the last state."""
    def body(S, x):
        o, S = gdn_step(S, *x)
        return S, o

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(body, S, xs)
    return jnp.moveaxis(o, 0, 1), S


def gdn_chunked(S, q, k, v, g, beta):
    """The chunked form over ``T`` tokens (shapes as :func:`gdn_scan`; ``T``
    is padded to whole chunks here with tokens that change nothing).
    Returns ``o [B, T, H, dv]`` float32 and the state after the last
    token."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    C = CHUNK
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
    G = jnp.cumsum(g, axis=-1)                              # [B, H, NC, C]
    col = jnp.arange(C)
    lower = col[:, None] > col[None, :]
    upto = lower | (col[:, None] == col[None, :])
    # exp(G_t - G_r) where t >= r (an exponent <= 0), 0 above the diagonal
    D = jnp.where(upto, jnp.exp(jnp.where(
        upto, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    A = jnp.where(lower, _mm("...td,...rd->...tr", k, k) * D, 0.0)
    Bm = _mm("...td,...rd->...tr", q, k) * D
    Tm = _unit_lower_inverse(A * beta[..., None, :])
    decay = jnp.exp(G)[..., None]                           # [B,H,NC,C,1]
    Wv = _mm("...tr,...rv->...tv", Tm, v)
    Wk = _mm("...tr,...rd->...td", Tm, k * decay)
    Gend = G[..., -1]                                       # [B, H, NC]
    Kbar = k * jnp.exp(Gend[..., None] - G)[..., None]

    def body(S, x):
        Wv_c, Wk_c, qt_c, Bm_c, beta_c, Kbar_c, dec_c = x
        U = (Wv_c - _mm("bhtd,bhdv->bhtv", Wk_c, S)) * beta_c[..., None]
        o = (_mm("bhtd,bhdv->bhtv", qt_c, S)
             + _mm("bhtr,bhrv->bhtv", Bm_c, U))
        S = dec_c[..., None, None] * S + _mm("bhtd,bhtv->bhdv", Kbar_c, U)
        return S, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in
               (Wv, Wk, q * decay, Bm, beta, Kbar, jnp.exp(Gend)))
    S, o = jax.lax.scan(body, S.astype(jnp.float32), xs)   # o [NC,B,H,C,dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        B, NC * C, H, dv)
    return o[:, :T], S


# ------------------------------ the seat pool ------------------------------


def seat_group(H: int, dv: int) -> int:
    """Heads the pool lays side by side: the fewest that divide ``H`` and
    whose values fill whole lane tiles; 1 where none does (a plane is then
    padded, as any array is)."""
    for hp in range(1, H + 1):
        if H % hp == 0 and (hp * dv) % _LANES == 0:
            return hp
    return 1


def pool_shape(seats: int, H: int, dk: int, dv: int) -> tuple:
    hp = seat_group(H, dv)
    return (seats, H // hp, dk, hp * dv)


def to_pool(S: jax.Array) -> jax.Array:
    """``[..., H, dk, dv]`` as the pool lays it: ``[..., H / hp, dk, hp *
    dv]``, head ``p * hp + i`` in lanes ``i * dv .. (i + 1) * dv`` of group
    ``p``."""
    *lead, H, dk, dv = S.shape
    hp = seat_group(H, dv)
    n = len(lead)
    S = S.reshape(*lead, H // hp, hp, dk, dv)
    S = jnp.swapaxes(S, n + 1, n + 2)
    return S.reshape(*lead, H // hp, dk, hp * dv)


def from_pool(P: jax.Array, H: int) -> jax.Array:
    """:func:`to_pool` back: ``[..., H / hp, dk, hp * dv] -> [..., H, dk,
    dv]``."""
    *lead, Hp, dk, W = P.shape
    hp = H // Hp
    n = len(lead)
    P = P.reshape(*lead, Hp, dk, hp, W // hp)
    P = jnp.swapaxes(P, n + 1, n + 2)
    return P.reshape(*lead, H, dk, W // hp)


def _step_kernel(seats_ref, fresh_ref, cols_ref, rows_ref, s_ref, o_ref,
                 s_out_ref, *, heads: int, group: int):
    """One row.  ``cols_ref [1, dk, 3H padded]`` holds, as columns, each
    head's key, step size times key and query (``H`` columns each, in that
    order): the vectors that multiply the state along ``dk``.  ``rows_ref
    [1, 2, H / hp, hp * dv]`` holds what runs along the lanes: the decay of
    each lane's head, and the values.  A group's plane ``[dk, hp * dv]`` is
    worked on as it lies; where it holds several heads a column is chosen
    lane by lane."""
    b = pl.program_id(0)
    cols = cols_ref[0]                                      # [dk, 3H..]
    fresh = fresh_ref[b] > 0
    W = s_ref.shape[-1]
    dv = W // group
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // dv

    def col(j, p):           # vector j of group p's heads, [dk, W]
        at = j * heads + p * group
        c = cols[:, at:at + 1]
        for i in range(1, group):
            c = jnp.where(lane_head >= i, cols[:, at + i:at + i + 1], c)
        return c

    for p in range(heads // group):
        S = jnp.where(fresh, 0.0, s_ref[0, p]) * rows_ref[0, 0, p:p + 1, :]
        u = rows_ref[0, 1, p:p + 1, :] - jnp.sum(
            S * col(0, p), axis=0, keepdims=True)
        S = S + col(1, p) * u
        s_out_ref[0, p] = S
        o_ref[0, p:p + 1, :] = jnp.sum(S * col(2, p), axis=0, keepdims=True)


def gdn_step_seats(pool, seats, fresh, q, k, v, g, beta, *,
                   interpret: bool = False):
    """:func:`gdn_step` on the rows' seats of ``pool [S + 1, H / hp, dk, hp
    * dv]`` float32 (:func:`to_pool`'s layout), in place: row ``b`` reads
    and writes ``pool[seats[b]]`` (from zeros where ``fresh[b]``), into
    on-chip memory once and back once.  ``q``, ``k`` ``[B, H, dk]``, ``v [B,
    H, dv]``, ``g``, ``beta`` ``[B, H]``.  Returns ``o [B, H, dv]`` and the
    pool.

    Rows that share a seat (the trash seat's pad rows) must bring ``g = 0``
    and ``beta = 0``: each then writes back the bytes it read.  The small
    operands are laid out here, in XLA, so that the kernel transposes
    nothing: the columns ``[B, dk, 3H]`` padded to whole lane tiles, the
    decay and the values as the pool's lanes run."""
    B, H, dk = k.shape
    dv = v.shape[-1]
    Hp, W = pool.shape[1], pool.shape[3]
    hp = H // Hp
    f32 = jnp.float32
    k = k.astype(f32)
    cols = jnp.concatenate(
        [k, beta.astype(f32)[..., None] * k, q.astype(f32)], axis=1)
    cols = jnp.swapaxes(cols, 1, 2)                         # [B, dk, 3H]
    L = -(-3 * H // _LANES) * _LANES
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, L - 3 * H)))
    dec = jnp.broadcast_to(jnp.exp(g.astype(f32))[..., None], (B, H, dv))
    rows = jnp.stack([dec.reshape(B, Hp, W),
                      v.astype(f32).reshape(B, Hp, W)], axis=1)
    row3 = lambda b, seats, fresh: (b, 0, 0)                # noqa: E731
    row4 = lambda b, seats, fresh: (b, 0, 0, 0)             # noqa: E731
    seat = lambda b, seats, fresh: (seats[b], 0, 0, 0)      # noqa: E731
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=H, group=hp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, dk, L), row3),
                      pl.BlockSpec((1, 2, Hp, W), row4),
                      pl.BlockSpec((1, Hp, dk, W), seat)],
            out_specs=[pl.BlockSpec((1, Hp, W), row3),
                       pl.BlockSpec((1, Hp, dk, W), seat)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hp, W), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: seats, fresh, cols, rows, pool -> the pool is output 1
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="gdn_step",
    )(seats.astype(jnp.int32), fresh.astype(jnp.int32), cols, rows, pool)
    return o.reshape(B, H, dv), pool


# ------------------------------ the conv tails ------------------------------
#
# The device keeps a pool ``[S + 1, K - 1, C]`` with the SEATS on the
# sublanes: a shape whose second-minor dimension is under a tile gets the
# order ``[K - 1][S + 1][C]`` from XLA's TPU client (``{2,0,1:T(8,128)(2,1)}``
# for ``bf16[97, 3, 11520]``).  A kernel that takes a seat's ``[1, K - 1, C]``
# block by ``seats[b]`` therefore has the whole pool relaid before and after
# every call, as XLA's own gather and scatter by seat do.  So the step below
# takes the pool as it lies, whole, a lane chunk at a time, and moves the
# rows' tails between seat order and row order on chip, by 0 / 1 products.

_CONV_LANES = 1280           # lanes a grid step holds of every seat's tail


def _conv_kernel(seats_ref, fresh_ref, valid_ref, u_ref, w_ref, tail_ref,
                 y_ref, tail_out_ref):
    """One lane chunk of every seat: ``tail_ref [K - 1, S + 1, c]`` the
    pool's taps, oldest first, ``u_ref [B, c]`` the rows' new inputs,
    ``w_ref [K, c]`` float32 taps; ``seats``, ``fresh``, ``valid`` ``[B,
    1]``.  ``take [B, S + 1]`` has a one where a row sits, ``put [S + 1,
    B]`` where a row with a valid token does: a product with either moves
    whole rows and changes no value (a one times ``x`` plus zeros, summed
    in float32).  The sum runs oldest tap first, as ``short_conv``'s."""
    f32 = jnp.float32
    taps, S1, _ = tail_ref.shape
    B = u_ref.shape[0]
    u = u_ref[...]
    dt = u.dtype             # the tails go through it, as short_conv's do
    hot = jax.lax.broadcasted_iota(jnp.int32, (B, S1), 1) == seats_ref[...]
    take = hot.astype(dt)
    put = jnp.logical_and(hot, valid_ref[...] > 0).astype(dt).T
    held = jnp.sum(put.astype(f32), axis=1, keepdims=True) > 0   # [S1, 1]
    # bfloat16 times a one is exact as it is; float32 needs every pass
    exact = dict(preferred_element_type=f32, precision=(
        jax.lax.Precision.HIGHEST if dt == f32 else None))
    fresh = fresh_ref[...] > 0
    x = [jnp.where(fresh, 0.0, jnp.dot(take, tail_ref[j].astype(dt), **exact))
         for j in range(taps)] + [u.astype(f32)]
    w = w_ref[...]
    y = x[0] * w[0:1]
    for j in range(1, taps + 1):
        y = y + x[j] * w[j:j + 1]
    y_ref[...] = y
    for j in range(taps):
        new = jnp.dot(put, x[j + 1].astype(dt), **exact)
        tail_out_ref[j] = jnp.where(held, new.astype(tail_out_ref.dtype),
                                    tail_ref[j])


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_step_seats(pool, seats, fresh, valid, u, w, *,
                    interpret: bool = False):
    """``delta_rule.short_conv`` for one token a row on the rows' seats of
    ``pool [S + 1, K - 1, C]``, in place and in one pass over the pool: row
    ``b`` reads ``pool[seats[b]]`` (zeros where ``fresh[b]``) and, where
    ``valid[b]``, leaves there its last ``K - 2`` inputs and ``u[b]``.  A
    seat that no valid row holds (a pad's, a dead row's, the trash seat
    with its many rows) keeps its bytes; at most one valid row holds a
    seat.  ``u [B, C]`` the rows' new inputs, ``w [K, C]`` the taps, oldest
    first.  Returns ``y [B, C]`` float32, ``short_conv``'s sum in its
    order, and the pool: the values are ``short_conv``'s, the tails to the
    bit (but a ``-0.0`` comes back ``+0.0``).

    What a product costs in exchange for the gathers: a seat's NaN or
    infinity reaches every row's ``y`` (``0 * inf``), where the gather kept
    it to the row that holds the seat.

    Jitted, so the layers of one step program share ONE lowered function
    (a kernel lowered at every call site is paid for in every compile)."""
    B, C = u.shape
    K, S1 = w.shape[0], pool.shape[0]
    c = C
    if C % _LANES == 0:      # the largest whole-tile divisor within reach
        c = max(d for d in range(_LANES, min(C, _CONV_LANES) + 1, _LANES)
                if C % d == 0)
    rows = jnp.swapaxes(pool, 0, 1)     # [K - 1, S + 1, C]: as it lies
    col = lambda a: a.astype(jnp.int32)[:, None]            # noqa: E731
    whole = lambda i: (0, 0)                                # noqa: E731
    lanes = lambda i: (0, i)                                # noqa: E731
    y, rows = pl.pallas_call(
        _conv_kernel,
        grid=(C // c,),
        in_specs=[pl.BlockSpec((B, 1), whole), pl.BlockSpec((B, 1), whole),
                  pl.BlockSpec((B, 1), whole), pl.BlockSpec((B, c), lanes),
                  pl.BlockSpec((K, c), lanes),
                  pl.BlockSpec((K - 1, S1, c), lambda i: (0, 0, i))],
        out_specs=[pl.BlockSpec((B, c), lanes),
                   pl.BlockSpec((K - 1, S1, c), lambda i: (0, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, C), jnp.float32),
                   jax.ShapeDtypeStruct(rows.shape, rows.dtype)],
        # operands: seats, fresh, valid, u, w, rows -> rows is output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="gdn_conv_step",
    )(col(seats), col(fresh), col(valid), u, w.astype(jnp.float32), rows)
    return y, jnp.swapaxes(rows, 0, 1)


def conv_seats(pool, seats, fresh, n, parts, p, *, kernel=None):
    """The layer's short convolution over ``u [B, T, C]``, the ``parts``
    (``q``, ``k``, ``v``) side by side, on the rows' seats of ``pool [S + 1,
    K - 1, C]``: ``y [B, T, C]`` float32 and the pool with each row's seat
    holding the ``K - 1`` inputs before position ``n[b]`` (``n [B]`` counts
    a row's valid tokens).  ``p`` the layer's parameters, of which this
    reads the taps ``gdn_conv`` (here and not at the call: a table's slice
    is an op of its own, and a chunk's program keeps the order it had).
    ``kernel`` as in ``model.gated_delta_attention``: where it is given a
    decode step (``T`` 1) runs :func:`conv_step_seats` ("pallas-seats" in
    ``/health``); a chunk, and the XLA path, gather the seats' tails and
    hand them to ``short_conv`` ("xla-gather")."""
    if parts[0].shape[1] == 1 and kernel is not None:
        u = jnp.concatenate(parts, axis=-1)
        y, pool = conv_step_seats(pool, seats, fresh, n > 0, u[:, 0],
                                  p["gdn_conv"], interpret=kernel)
        return y[:, None], pool
    prev = jnp.where(fresh[:, None, None], 0, jnp.take(pool, seats, axis=0))
    y, nxt = short_conv(jnp.concatenate(parts, axis=-1), prev, p["gdn_conv"],
                        n)
    return y, pool.at[seats].set(nxt.astype(pool.dtype))
