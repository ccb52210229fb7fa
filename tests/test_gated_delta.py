"""The gated delta rule (``ops/gated_delta.py``; PR 45): the chunked form
and the seat kernel against the token-by-token recurrence, at small sizes
with ``dk != dv`` and a head count that is no power of two; the conv tails'
decode step (PR 55) against ``short_conv``.  CPU, float32; the Pallas
kernels interpreted.  ``python -m tests.test_gated_delta`` runs the conv
step's cases compiled, on the chip."""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import gated_delta as GD
from dynamo_tpu.ops.delta_rule import short_conv


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


# ------------------------------ the conv tails ------------------------------

CONV_CASES = [
    # B, C, seats, the pool's dtype
    (8, 256, 12, jnp.bfloat16),
    (8, 256, 12, jnp.float32),
    (8, 11520, 9, jnp.bfloat16),        # the published width, few rows
    (128, 1280, 96, jnp.bfloat16),      # the cell's rows and seats
    (128, 11520, 96, jnp.bfloat16),     # the cell's shapes
    (128, 11520, 96, jnp.float32),
]


def _conv_case(B, C, S, dtype, K=4):
    """Rows on seats out of order; rows 0 and 2 fresh (from zeros whatever
    the seat held); row 1 live with no valid token; the last three rows (at
    least) dead on the trash seat ``S``; some seats held by nobody.  The
    pool holds what a bfloat16 model wrote, in ``dtype``."""
    ks = jax.random.split(jax.random.PRNGKey(B + C + S), 4)
    pool = jax.random.normal(ks[0], (S + 1, K - 1, C), jnp.float32).astype(
        jnp.bfloat16).astype(dtype)
    live = min(B - 3, S - 2)
    seats = jnp.concatenate([jax.random.permutation(ks[1], S)[:live],
                             jnp.full((B - live,), S)]).astype(jnp.int32)
    valid = (jnp.arange(B) < live).at[1].set(False)
    fresh = jnp.zeros((B,), bool).at[0].set(True).at[2].set(True)
    u = jax.random.normal(ks[2], (B, C), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(ks[3], (K, C), jnp.float32).astype(jnp.bfloat16)
    return pool, seats, fresh, valid, u, w


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _conv_check(B, C, S, dtype, interpret):
    """``conv_step_seats`` against ``short_conv`` at ``T = 1`` over the
    gathered tails: what differs, by name (nothing, where it is right)."""
    pool, seats, fresh, valid, u, w = _conv_case(B, C, S, dtype)
    y, pool1 = GD.conv_step_seats(pool, seats, fresh, valid, u, w,
                                  interpret=interpret)
    prev = jnp.where(fresh[:, None, None], 0, pool[seats])
    y_ref, nxt = short_conv(u[:, None], prev, w, valid.astype(jnp.int32))
    held = np.asarray(seats)[np.asarray(valid)]
    idle = np.setdiff1d(np.arange(S + 1), held)
    assert S in idle and len(idle) > 1
    return {
        "y": bool(y.shape == (B, C) and y.dtype == jnp.float32
                  and np.array_equal(np.asarray(y), np.asarray(y_ref[:, 0]))),
        "tails": np.array_equal(
            _bits(pool1[held]),
            _bits(nxt.astype(dtype)[np.asarray(valid)])),
        "idle_seats": np.array_equal(_bits(pool1[idle]), _bits(pool[idle])),
    }


@pytest.mark.parametrize("B,C,S,dtype", CONV_CASES)
def test_the_conv_step_is_short_conv_on_the_rows_seats(B, C, S, dtype):
    """Interpreted: the new tails bit-equal, ``y`` equal, and every seat no
    valid row holds (the trash seat with its several rows among them)
    untouched."""
    assert _conv_check(B, C, S, dtype, True) == {
        "y": True, "tails": True, "idle_seats": True}


def test_conv_seats_takes_the_kernel_for_a_decode_step_alone():
    """``conv_seats`` is the one place that decides: the kernel at ``T = 1``
    where ``kernel`` is given, else ``short_conv`` over the gathered tails;
    both give the same bits."""
    pool, seats, fresh, valid, u, w = _conv_case(8, 256, 12, jnp.bfloat16)
    n = valid.astype(jnp.int32)
    parts = (u[:, None, :64], u[:, None, 64:192], u[:, None, 192:])
    p = {"gdn_conv": w}
    y0, p0 = GD.conv_seats(pool, seats, fresh, n, parts, p)
    y1, p1 = GD.conv_seats(pool, seats, fresh, n, parts, p, kernel=True)
    assert y1.shape == y0.shape == (8, 1, 256)
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    # the gather's rows on the trash seat scatter what they read there too
    assert np.array_equal(_bits(p0), _bits(p1))


def main() -> int:
    """The cases compiled, on the device this process holds."""
    bad = 0
    for B, C, S, dtype in CONV_CASES:
        got = _conv_check(B, C, S, dtype, False)
        bad += not all(got.values())
        print(json.dumps({"device": jax.devices()[0].device_kind, "B": B,
                          "C": C, "seats": S,
                          "pool": jnp.dtype(dtype).name, **got}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
