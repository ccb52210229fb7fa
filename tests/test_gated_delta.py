"""The gated delta rule (``ops/gated_delta.py``; PR 45): the chunked form
and the seat kernel against the token-by-token recurrence, at small sizes
with ``dk != dv`` and a head count that is no power of two.  CPU, float32;
the Pallas kernel interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import gated_delta as GD


def _inputs(seed, B, T, H, dk, dv, beta_hi=2.0, decay=(0.9, 0.999)):
    """Keys and queries as the layer makes them (unit keys, scaled unit
    queries), log decays of a memory of ten to a thousand tokens, step
    sizes in (0, ``beta_hi``)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = jnp.log(jax.random.uniform(ks[3], (B, T, H), minval=decay[0],
                                   maxval=decay[1]))
    beta = beta_hi * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S = 0.5 * jax.random.normal(ks[5], (B, H, dk, dv))
    return S, q, k, v, g, beta


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.sum((a - b) ** 2) / np.sum(b ** 2)))


@pytest.mark.parametrize("T,H,dk,dv", [
    (64, 3, 12, 24),        # one whole chunk
    (150, 3, 12, 24),       # no multiple of the chunk: two chunks and 22
    (7, 5, 8, 16),          # shorter than a chunk
    (200, 6, 24, 64),       # the sizes the pool pairs heads at
])
def test_the_chunked_form_is_the_token_recurrence(T, H, dk, dv):
    S, q, k, v, g, beta = _inputs(T, 2, T, H, dk, dv)
    o_ref, S_ref = GD.gdn_scan(S, q, k, v, g, beta)
    o, S1 = jax.jit(GD.gdn_chunked)(S, q, k, v, g, beta)
    assert o.shape == (2, T, H, dv)
    assert _rel(o, o_ref) < 2e-5
    assert _rel(S1, S_ref) < 2e-5


def test_step_sizes_near_two_and_equal_keys_stay_bounded():
    """``beta`` near 2 reflects the state along the key (eigenvalue -1);
    with one key repeated through a chunk the system's off-diagonal is
    ``beta`` everywhere, where an inverse by powers would grow and cancel."""
    B, T, H, dk, dv = 1, 130, 3, 12, 24
    S, q, k, v, g, _ = _inputs(5, B, T, H, dk, dv)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full((B, T, H), 1.999)
    g = jnp.full((B, T, H), -1e-3)
    o_ref, S_ref = GD.gdn_scan(S, q, k, v, g, beta)
    o, S1 = jax.jit(GD.gdn_chunked)(S, q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert _rel(o, o_ref) < 1e-4 and _rel(S1, S_ref) < 1e-4


def test_pads_leave_the_state_bit_equal():
    """A pad brings decay 1 (``g = 0``) and step size 0: behind a row's
    valid prefix the chunked form hands back the state of its last valid
    token, and a row of pads alone the state it was given, to the bit."""
    B, T, H, dk, dv = 2, 100, 3, 12, 24
    S, q, k, v, g, beta = _inputs(9, B, T, H, dk, dv)
    n = 37
    live = (jnp.arange(T) < n)[None, :, None]
    g_p, beta_p = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    chunked = jax.jit(GD.gdn_chunked)
    _, S_pad = chunked(S, q, k, v, g_p, beta_p)
    _, S_cut = chunked(S, q[:, :n], k[:, :n], v[:, :n], g[:, :n],
                       beta[:, :n])
    assert _rel(S_pad, S_cut) < 1e-6
    zeros = jnp.zeros_like(g)
    _, S_same = chunked(S, q, k, v, zeros, zeros)
    assert np.array_equal(np.asarray(S_same), np.asarray(S))
    _, S_step = GD.gdn_step(S, q[:, 0], k[:, 0], v[:, 0], zeros[:, 0],
                            zeros[:, 0])
    assert np.array_equal(np.asarray(S_step), np.asarray(S))


@pytest.mark.parametrize("H,dk,dv,group", [
    (3, 12, 24, 1),         # no group fills a lane tile: planes as they are
    (6, 24, 64, 2),         # two heads side by side, as at 30 heads of 192
    (4, 8, 128, 1),         # a head fills a tile alone
])
def test_the_pool_lays_heads_side_by_side_and_back(H, dk, dv, group):
    assert GD.seat_group(H, dv) == group
    assert GD.seat_group(30, 192) == 2
    assert GD.pool_shape(97, 30, 96, 192) == (97, 15, 96, 384)
    S = jax.random.normal(jax.random.PRNGKey(0), (5, H, dk, dv))
    P = GD.to_pool(S)
    assert P.shape == GD.pool_shape(5, H, dk, dv)
    # head p * hp + i lies in lanes i * dv .. of group p
    h = H - 1
    p, i = divmod(h, group)
    assert np.array_equal(np.asarray(P[2, p, :, i * dv:(i + 1) * dv]),
                          np.asarray(S[2, h]))
    assert np.array_equal(np.asarray(GD.from_pool(P, H)), np.asarray(S))


@pytest.mark.parametrize("H,dk,dv", [(3, 12, 24), (6, 24, 64)])
def test_the_seat_kernel_is_the_step_on_the_rows_seats(H, dk, dv):
    """Interpreted: rows on seats out of order, a fresh row (starts from
    zeros whatever its seat held), two pad rows on the trash seat; the
    seats nobody held keep their bytes."""
    seats_n, B = 7, 5
    S, q, k, v, g, beta = _inputs(11, B, 1, H, dk, dv)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    states = jax.random.normal(jax.random.PRNGKey(3), (seats_n, H, dk, dv))
    pool = GD.to_pool(states)
    seats = jnp.asarray([4, 1, 6, 6, 2], jnp.int32)      # 6: the trash seat
    fresh = jnp.asarray([0, 1, 0, 0, 0], bool)
    pad = jnp.asarray([0, 0, 1, 1, 0], bool)
    g = jnp.where(pad[:, None], 0.0, g)
    beta = jnp.where(pad[:, None], 0.0, beta)
    o, pool1 = GD.gdn_step_seats(pool, seats, fresh, q, k, v, g, beta,
                                 interpret=True)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, states[seats])
    o_ref, s1 = GD.gdn_step(s0, q, k, v, g, beta)
    assert o.shape == (B, H, dv)
    assert _rel(o, o_ref) < 1e-6
    got = GD.from_pool(pool1, H)
    for b in (0, 1, 4):
        assert _rel(got[seats[b]], s1[b]) < 1e-6
    for seat in (0, 3, 5, 6):           # never held, or held by pads alone
        assert np.array_equal(np.asarray(got[seat]),
                              np.asarray(states[seat]))
