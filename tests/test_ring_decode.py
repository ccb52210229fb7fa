"""Device token ring: packed prefill + autopilot decode windows (the path
that serves) reproduce the synchronous step path token-for-token (greedy
and seeded sampling), cap write-back, and trash-slot semantics. CPU, single
device."""

import numpy as np
import jax
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine import model as model_lib


@pytest.fixture(scope="module")
def setup():
    mc = ModelConfig.tiny()
    ec = EngineConfig(
        num_blocks=64, max_model_len=128, max_num_batched_tokens=32,
        prefill_buckets=(32,), decode_buckets=(4,), max_num_seqs=4,
    )
    params = model_lib.init_params(jax.random.PRNGKey(0), mc)
    return mc, ec, params


def _prompt(n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, size=n).astype(np.int32)


T, W = 32, 8     # the prefill chunk and its table's width


def _sync_generate(mc, ec, params, prompt, n_decode, temperature=0.0,
                   seed=-1):
    """Reference: the synchronous unified-step path."""
    step = model_lib.make_step_fn(mc, ec, None)
    cache = model_lib.init_cache(mc, ec)
    bs = ec.block_size
    table = list(range(1, 1 + (len(prompt) + n_decode) // bs + 2))
    tokens = np.zeros((1, T), np.int32)
    positions = np.full((1, T), -1, np.int32)
    tokens[0, :len(prompt)] = prompt
    positions[0, :len(prompt)] = np.arange(len(prompt))
    tables = np.zeros((1, W), np.int32)
    tables[0, :len(table)] = table
    temp = np.array([temperature], np.float32)
    tk = np.zeros((1,), np.int32)
    tp = np.ones((1,), np.float32)
    sd = np.array([seed], np.int32)
    rng = jax.random.PRNGKey(7)
    cache, sampled = step(
        params, cache, tokens, positions, tables,
        np.array([len(prompt) - 1], np.int32), rng, temp, tk, tp, sd,
    )
    out = [int(np.asarray(sampled)[0])]
    pos = len(prompt)
    for i in range(n_decode - 1):
        tok = np.array([[out[-1]]], np.int32)
        rng, sub = jax.random.split(rng)
        cache, sampled = step(
            params, cache, tok, np.array([[pos]], np.int32), tables,
            np.zeros((1,), np.int32), sub, temp, tk, tp, sd,
        )
        out.append(int(np.asarray(sampled)[0]))
        pos += 1
    return out


def _join(ec, delta_fn, ctl, rows):
    """One packed control delta: ``rows`` of (slot, pos, valid_until,
    last_tok (-1 = keep the ring's), table row, temperature, seed)."""
    Wcap = ec.max_blocks_per_seq
    di = np.zeros((len(rows), model_lib.CTL_I32_FIELDS + Wcap), np.int32)
    df = np.ones((len(rows), 2), np.float32)
    for i, (slot, pos, vu, lt, table, temp, seed) in enumerate(rows):
        di[i, :6] = (slot, pos, vu, 0, seed, lt)
        di[i, 6:6 + len(table)] = table
        df[i, 0] = temp
    return delta_fn(ctl, di, df)


def _ring_generate(mc, ec, params, prompt, n_decode, temperature=0.0,
                   seed=-1):
    """Ring path: the packed prefill writes the slot, decode windows chain
    on device. The host feeds NO tokens after the prompt (the join's delta
    keeps the ring's token)."""
    S = ec.max_num_seqs
    prefill = model_lib.make_packed_prefill_fn(mc, ec, T, W, None)
    window_fn, delta_fn = model_lib.make_autopilot_fns(
        mc, ec, ec.max_blocks_per_seq, None)
    cache = model_lib.init_cache(mc, ec)
    ctl = jax.device_put(model_lib.init_ctl(
        ec, S, ec.max_blocks_per_seq, seed=7))
    bs = ec.block_size
    table = list(range(1, 1 + (len(prompt) + n_decode) // bs + 2))
    slot = 2   # arbitrary live slot
    pint = np.zeros((1, T + W + model_lib.PP_SCALARS), np.int32)
    pint[0, :len(prompt)] = prompt
    pint[0, T:T + len(table)] = table
    pint[0, T + W:] = (
        len(prompt), 0, slot, 1, 0, seed,
        int(round(temperature * model_lib.PP_QUANT)),
        int(round(1.0 * model_lib.PP_QUANT)),
    )
    cache, last_tok, sampled = prefill(
        params, cache, ctl["last_tok"], pint, jax.random.PRNGKey(7))
    out = [int(np.asarray(sampled)[0])]
    assert int(np.asarray(last_tok)[slot]) == out[0]
    ctl = _join(ec, delta_fn, {**ctl, "last_tok": last_tok}, [
        (slot, len(prompt), ec.max_model_len, -1, table, temperature, seed),
    ])
    rows = np.array([slot, S, S, S], np.int32)   # pads ride the trash seat
    for _ in range(n_decode - 1):
        cache, ctl, samples = window_fn(params, cache, ctl, rows)
        assert samples.shape == (1, 4)
        out.append(int(np.asarray(samples)[0, 0]))
    assert int(np.asarray(ctl["pos"])[slot]) == len(prompt) + n_decode - 1
    return out


def test_ring_matches_sync_greedy(setup):
    mc, ec, params = setup
    prompt = _prompt(12, mc.vocab_size)
    ref = _sync_generate(mc, ec, params, prompt, 9)
    got = _ring_generate(mc, ec, params, prompt, 9)
    assert got == ref, (got, ref)


def test_ring_matches_sync_seeded(setup):
    """Seeded stochastic rows are position-keyed, so the ring path must
    reproduce the sync path exactly even with temperature > 0."""
    mc, ec, params = setup
    prompt = _prompt(10, mc.vocab_size, seed=3)
    ref = _sync_generate(mc, ec, params, prompt, 8, temperature=0.8,
                         seed=1234)
    got = _ring_generate(mc, ec, params, prompt, 8, temperature=0.8,
                         seed=1234)
    assert got == ref


def _four_seats(mc, ec, ring_fill, rows):
    """An autopilot window over four joined seats (input token 5, position
    ``pos0``, ``valid_until`` as ``rows`` gives it) on a ring pre-filled
    with ``ring_fill``."""
    S = ec.max_num_seqs
    window_fn, delta_fn = model_lib.make_autopilot_fns(
        mc, ec, ec.max_blocks_per_seq, None)
    cache = model_lib.init_cache(mc, ec)
    ctl = model_lib.init_ctl(ec, S, ec.max_blocks_per_seq)
    ctl["last_tok"] = np.full((S + 1,), ring_fill, np.int32)
    ctl = _join(ec, delta_fn, jax.device_put(ctl), rows)
    return window_fn, cache, ctl


def test_window_capacity_writeback(setup):
    """A row at capacity keeps its LAST VALID sample in the ring and its
    position where it stands, not the garbage computed past valid_until."""
    mc, ec, params = setup
    pos0 = 10
    table = list(range(1, 9))
    # seat 0: one more step fits (valid_until = pos0 + 1), the rest have room
    window_fn, cache, ctl = _four_seats(mc, ec, 0, [
        (b, pos0, pos0 + 1 if b == 0 else 128, 5, table, 0.0, -1)
        for b in range(4)
    ])
    slots = np.arange(4, dtype=np.int32)
    cache, ctl, first = window_fn(params, cache, ctl, slots)
    cache, ctl, second = window_fn(params, cache, ctl, slots)
    first, second = np.asarray(first), np.asarray(second)
    lt, pos = np.asarray(ctl["last_tok"]), np.asarray(ctl["pos"])
    assert lt[0] == first[0, 0] and pos[0] == pos0 + 1   # capped at one
    assert (lt[1:4] == second[0, 1:]).all()              # both windows
    assert (pos[1:4] == pos0 + 2).all()


def test_trash_slot(setup):
    """Rows on the trash seat write there; live slots are unaffected."""
    mc, ec, params = setup
    S = ec.max_num_seqs
    window_fn, cache, ctl = _four_seats(mc, ec, 77, [
        (0, 4, 128, 5, list(range(1, 9)), 0.0, -1),
    ])
    rows = np.array([0, S, S, S], np.int32)  # rows 1-3 disowned
    cache, ctl, samples = window_fn(params, cache, ctl, rows)
    lt = np.asarray(ctl["last_tok"])
    assert lt[0] == np.asarray(samples)[0, 0]
    assert all(lt[i] == 77 for i in range(1, S))  # untouched live slots
    assert np.asarray(ctl["pos"])[S] == 0          # the trash seat stands
