"""Plain reference of a hybrid decoder as ``allenai/Olmo-Hybrid-7B``
publishes one (``model_type`` ``olmo_hybrid``; ``configs/olmo-hybrid-7b-
l8.json`` names this module).

Every layer, with ``x`` the residual stream (the family's reordered norm:
a sublayer reads the RAW stream and its OUTPUT is normed)::

    h = x + rmsnorm(Mixer(x));   y = h + rmsnorm(SwiGLU(h))

then a final norm and the untied head.  The mixers by ``layer_types``:

- ``linear_attention`` (the gated delta rule, arXiv:2412.06464), ``H`` heads
  of ``dk`` = ``linear_key_head_dim`` by ``dv`` = ``linear_value_head_dim``:
  ``q | k | v = silu(conv(x Wq | x Wk | x Wv))``, the convolution causal and
  depthwise over time with ``linear_conv_kernel_dim`` taps, ``y_t = sum_j
  w[j] u_{t - 3 + j}`` (inputs before the sequence are zeros); ``q``, ``k``
  divided by their norm a head, ``q`` times ``dk^-0.5``; ``beta = sigmoid(x
  Wb)`` a head, times 2 where ``linear_allow_neg_eigval``; ONE log decay a
  head ``g = -exp(A_log[h]) softplus(x Wa + dt_bias[h])``; a state ``S [dk,
  dv]`` a head from zeros, token by token: ``S <- exp(g_t) S; S <- S +
  beta_t k_t (v_t - S^T k_t)^T; o_t = S^T q_t``; ``o`` RMS-normed over each
  head with a weight of ``dv``, times ``silu(x Wg)`` a channel, then ``Wo``.
  No rope.
- ``full_attention``: ``q = rmsnorm(x Wq)``, ``k = rmsnorm(x Wk)``, each
  norm over the WHOLE projection (heads together), ``v = x Wv``; ``H`` heads
  and as many KV heads of ``head_dim``; causal softmax attention at
  ``head_dim^-0.5``; ``Wo``.  No rotary embedding (``rope_theta`` null).

What the published config leaves open is listed in the configuration's file
under ``assumed``; this module follows the same list.

Everything is float32 ``jax.numpy`` at ``highest`` matmul precision, one
sequence at a time, no cache, no chunks, no kernels, nothing of
``engine/model.py`` or ``ops/``: the recurrence is a ``lax.scan`` over the
whole sequence, one token a step.  ``rms_norm``, ``swiglu``, the float32
cast and the head in column blocks are ``references/laguna.py``'s.

It reads the engine's leaves by name (``model._init_table_small``):
``attn_norm mlp_norm [L, D]`` (here a layer's two OUTPUT norms); ``wq wo
q_norm`` one stack a kind under the kind's name; ``gdn_wk gdn_wv gdn_wa
gdn_wb gdn_wg gdn_conv gdn_a_log gdn_dt_bias gdn_o_norm`` one row a linear
layer; ``wk wv k_norm`` one row a full layer; ``w_gate w_up w_down`` one row
a layer.  And the state pool's layout (``ops/gated_delta.py``): ``[seats, H /
hp, dk, hp * dv]``, head ``p * hp + i`` in lanes ``i * dv ..`` of group
``p``; :func:`pool_states` undoes it with its own arithmetic.

``compare``: ``B`` = 2 seeded sequences of ``T`` = 1100 tokens are prefilled
through the engine's ``forward`` in chunks of 512 (the state and the
convolution's tail cross two chunk boundaries; the last chunk, 76 tokens,
is no multiple of the chunked form's 64), into K / V pages of the full
layers and seats 3 and 1 of a state pool of four (neither in order nor next
to each other, so a state read from or written to another seat shows); then
``N_DECODE`` = 64 tokens are decoded one at a time through the decode path
(the seat kernel and the paged kernel where the engine runs them).
Compared against this forward's over prompt + the tokens the engine chose:

- the logits at each chunk's first ``HEAD`` = 4 positions and its last
  (0-3, 511, 512-515, 1023, 1024-1027, 1099): just behind a boundary
  nothing carries the past but the seat's state, its convolution tail and
  the cache's pages;
- the logits of the 64 decode steps;
- the rows' seats read back after the last step, a linear layer each,
  against the state this module's recurrence ends with
  (``state.rms_rel_by_layer``) and the last three inputs of its convolution
  (``conv.rms_rel_max``), and the seats no row held, which must be the
  zeros they were made as (``state.stray_max``);
- of the first full layer, the page that starts at the second chunk and the
  last whole page of decoded tokens, K and V, against this module's normed
  keys and values at those positions (``kv.rms_rel``).

Tolerances.  ``REL_TOL`` = 40% of the largest reference logit on each phase
is the guard against a part of the mathematics gone (a wrong mask, a
dropped layer, ``beta`` undoubled: 92-109%).  A maximum over 3 million
logits swings: over 20 sound seeds the chip reads 4.1-17.3% at the prefill
probes and 3.3-4.3% in decode.  The prefill's swing is positions 0-3: at a
sequence's first tokens a head's output is ``beta (k.q) v``, smaller than
the per-head norm's ``eps``, so the rounding of a near-zero ``k.q`` passes
through un-normed; and this table rounds more than the pre-norm ones, since
every sublayer's bfloat16 output is normed to unit scale before it joins the
stream (a layer's state parts from the reference's by 0.3, 1.6, 3.2, 5.3,
6.5, 8.0% with depth).  The limits that refuse a lower precision are pooled
rms statistics that the first tokens do not move, steady to a few percent
from seed to seed, read on the chip and kept with their readings in
``limits/<configuration>.json``: a state pool kept in bfloat16 (the
configuration's file with ``linear_state_dtype`` "bfloat16") fails
``state.rms_rel_first`` 3.6-fold and ``decode.rms_rel``,
``conv.rms_rel_max`` and ``kv.rms_rel`` besides; ``variant``
"beta_undoubled" breaks THIS forward on purpose (the step size left in (0,
1)), and the served path, which doubles it, then reads as far from it as a
served path that forgot to would read from the sound reference: every
statistic near 100%.
"""

from __future__ import annotations

import dataclasses
import functools

from benchmarks.chip.references.laguna import (_f32, head_logits, rms_norm,
                                               swiglu)

REL_TOL = 0.4
T_PROMPT = 1100      # 512 + 512 + 76
N_DECODE = 64
CHUNK = 512
HEAD = 4             # positions compared just behind a chunk boundary

LINEAR, FULL = "linear_attention", "full_attention"


def gated_delta_mixer(x, w, *, heads, dk, dv, taps, neg_eigval, eps,
                      variant=""):
    """One sequence's gated-delta-rule mixer on the raw stream ``x [T, D]``
    (before ``Wo``'s output is normed: the caller's), the state ``[heads,
    dk, dv]`` its last token leaves, and the convolution's last ``taps - 1``
    inputs."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    u = jnp.concatenate([x @ w["wq"], x @ w["gdn_wk"], x @ w["gdn_wv"]], -1)
    past = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u], 0)
    y = jax.nn.silu(sum(w["gdn_conv"][j] * past[j:j + T]
                        for j in range(taps)))

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(y[:, :heads * dk].reshape(T, heads, dk)) * dk ** -0.5
    k = unit(y[:, heads * dk:2 * heads * dk].reshape(T, heads, dk))
    v = y[:, 2 * heads * dk:].reshape(T, heads, dv)
    g = -jnp.exp(w["gdn_a_log"]) * jax.nn.softplus(
        x @ w["gdn_wa"] + w["gdn_dt_bias"])                    # [T, heads]
    beta = jax.nn.sigmoid(x @ w["gdn_wb"])
    if neg_eigval and variant != "beta_undoubled":
        beta = 2.0 * beta

    def token(S, t):                   # S [heads, dk, dv]
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, None, None] * S
        delta = v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + (b_t[:, None] * k_t)[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv)),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * w["gdn_o_norm"]
    o = o.reshape(T, heads * dv) * jax.nn.silu(x @ w["gdn_wg"])
    return o @ w["wo"], S, past[T:]


def full_mixer(x, w, *, heads, kv, hd, eps):
    """One sequence's full attention on the raw stream ``x [T, D]``, with
    the normed keys and the values ``[T, kv, hd]`` it attends."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    q = rms_norm(x @ w["wq"], w["q_norm"], eps).reshape(T, heads, hd)
    k = rms_norm(x @ w["wk"], w["k_norm"], eps).reshape(T, kv, hd)
    v = (x @ w["wv"]).reshape(T, kv, hd)
    rep = heads // kv
    s = jnp.einsum("qnd,knd->nqk", q, jnp.repeat(k, rep, 1)) / jnp.sqrt(
        jnp.float32(hd))
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("nqk,knd->qnd", a, jnp.repeat(v, rep, 1))
    return o.reshape(T, heads * hd) @ w["wo"], k, v


def mixer_weights(cfg, layers: dict, li: int) -> dict:
    """The leaves of layer ``li``'s mixer and its output norm in float32,
    found by this module's own reading of the published lists."""
    kind = cfg.layer_types[li]
    at = cfg.layer_types[:li].count(kind)
    w = {"attn_norm": _f32(layers["attn_norm"][li])}
    for k in ("wq", "wo"):
        w[k] = _f32(layers[k][kind][at])
    if kind == LINEAR:
        for k in layers:
            if k.startswith("gdn_"):
                w[k] = _f32(layers[k][at])
    else:
        w["q_norm"] = _f32(layers["q_norm"][kind][at])
        for k in ("wk", "wv", "k_norm"):
            w[k] = _f32(layers[k][at])
    return w


def ffn_weights(layers: dict, li: int) -> dict:
    """Layer ``li``'s SwiGLU and its output norm in float32."""
    return {k: _f32(layers[k][li])
            for k in ("mlp_norm", "w_gate", "w_up", "w_down")}


@functools.lru_cache(maxsize=None)
def _mixer_jit(kind: str, **kw):
    import jax

    fn = gated_delta_mixer if kind == LINEAR else full_mixer
    return jax.jit(functools.partial(fn, **kw))


def reference_hidden(cfg, params, tokens, variant: str = ""):
    """Final-normed float32 hidden states ``[T, D]`` of ONE sequence; of
    each linear layer the state and the convolution tail its last token
    leaves; of the first full layer the keys and values ``[T, kv, hd]``."""
    import jax
    import jax.numpy as jnp

    layers = params["layers"]
    eps = cfg.rms_norm_eps
    states, tails, page = [], [], None
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        for li in range(cfg.num_layers):
            # one sublayer's float32 copy at a time (0.35 and 0.51 GB at
            # the published widths): the check runs beside a resident cache
            w = mixer_weights(cfg, layers, li)
            kind, heads = cfg.layer_types[li], cfg.num_heads_per_layer[li]
            if kind == LINEAR:
                out, S, tail = _mixer_jit(
                    kind, heads=heads, dk=cfg.linear_key_head_dim,
                    dv=cfg.linear_value_head_dim,
                    taps=cfg.linear_conv_kernel_dim,
                    neg_eigval=bool(cfg.linear_allow_neg_eigval), eps=eps,
                    variant=variant)(x, w)
                states.append(S)
                tails.append(tail)
            else:
                out, k, v = _mixer_jit(
                    kind, heads=heads, kv=cfg.num_kv_heads, hd=cfg.head_dim,
                    eps=eps)(x, w)
                if page is None:
                    page = (k, v)
            x = x + rms_norm(out, w["attn_norm"], eps)
            w = out = None
            w = ffn_weights(layers, li)
            x = x + rms_norm(swiglu(x, w["w_gate"], w["w_up"], w["w_down"]),
                             w["mlp_norm"], eps)
            w = None
        x = rms_norm(x, _f32(params["final_norm"]), eps)
    return x, states, tails, page


def pool_states(pool, heads: int):
    """The pool's rows ``[n, H / hp, dk, hp * dv]`` as ``[n, H, dk, dv]``:
    head ``p * hp + i`` lies in lanes ``i * dv .. (i + 1) * dv`` of group
    ``p``."""
    import numpy as np

    n, groups, dk, wide = pool.shape
    hp = heads // groups
    dv = wide // hp
    rows = np.asarray(pool, np.float32).reshape(n, groups, dk, hp, dv)
    return rows.transpose(0, 1, 3, 2, 4).reshape(n, heads, dk, dv)


@functools.lru_cache(maxsize=4)
def served_step(cfg, eng, mesh, at: tuple):
    """The program's ``forward`` + head on one chunk, the logits taken at
    the chunk's positions ``at``; jitted once for a configuration."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import model as M

    def run(params, cache, tok, p, tb, seats):
        cache, h = M.forward(cfg, eng, params, cache, tok, p, tb, mesh=mesh,
                             seats=seats)
        return cache, M.logits_fn(cfg, params, h[:, jnp.asarray(at)])

    return jax.jit(run, donate_argnums=(1,))


def chunk_probes(n: int) -> tuple:
    """The positions of a chunk of ``n`` tokens whose logits are compared:
    the first ``HEAD`` and the last."""
    return tuple(range(min(HEAD, n - 1))) + (n - 1,)


def served(engine, toks, chunk: int, n_decode: int):
    """What the program serves for ``toks [B, T]``: the prompt prefilled in
    chunks of ``chunk`` through ``forward``, K / V pages and a state pool of
    ``B + 2`` seats of which the rows hold ``B + 1, B - 1, ..``, then
    ``n_decode`` greedy tokens decoded one at a time.  Returns the logits
    at every chunk's probes ``[B, P, V]`` with their positions ``[P]``, the
    decode logits ``[B, n_decode, V]``, the tokens chosen ``[B, n_decode]``,
    each linear layer's states ``[linear layers, B, H, dk, dv]`` and
    convolution tails ``[linear layers, B, K - 1, C]`` of the rows' seats
    after the last fed token, the largest magnitude left in a seat no row
    held, and the first full layer's K and V pages ``[B, W, KV, bs, hd]`` by
    the rows' tables."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import model as M

    cfg, mesh = engine.model_config, engine.mesh
    B, T = toks.shape
    bs = engine.config.block_size
    nb = -(-(T + n_decode) // bs)
    eng = dataclasses.replace(engine.config, num_blocks=B * nb + 1,
                              max_num_seqs=B + 2)
    cache = M.init_cache(cfg, eng)
    W = max(eng.max_blocks_per_seq, nb)
    tables = np.zeros((B, W), np.int32)
    for b in range(B):
        tables[b, :nb] = 1 + b * nb + np.arange(nb)
    seats = (B + 1 - 2 * np.arange(B)).astype(np.int32)

    probes, where = [], []
    for t0 in range(0, T, chunk):
        t1 = min(t0 + chunk, T)
        at = chunk_probes(t1 - t0)
        pos = np.tile(np.arange(t0, t1, dtype=np.int32), (B, 1))
        cache, lg = served_step(cfg, eng, mesh, at)(
            engine.params, cache, toks[:, t0:t1], pos, tables, seats)
        probes.append(np.asarray(lg, np.float32))
        where.extend(t0 + a for a in at)
    step = served_step(cfg, eng, mesh, (0,))
    logits, chosen = [probes[-1][:, -1]], []
    for k in range(n_decode):
        nxt = np.argmax(logits[-1], -1).astype(np.int32)
        chosen.append(nxt)
        if k == n_decode - 1:
            break
        cache, lg = step(engine.params, cache, nxt[:, None],
                         np.full((B, 1), T + k, np.int32), tables, seats)
        logits.append(np.asarray(lg, np.float32)[:, 0])
    heads = cfg.num_heads_per_layer[cfg.layer_types.index(LINEAR)]
    states = np.stack([pool_states(np.asarray(pool[seats], np.float32),
                                   heads) for pool in cache["state"]])
    tails = np.stack([np.asarray(pool[seats], np.float32)
                      for pool in cache["conv"]])
    others = np.setdiff1d(np.arange(eng.max_num_seqs + 1), seats)
    stray = max(float(jnp.max(jnp.abs(pool[others].astype(jnp.float32))))
                for pool in cache["state"] + cache["conv"])
    pages = tuple(np.asarray(plane[tables[:, :nb].reshape(-1)], np.float32
                             ).reshape((B, nb) + plane.shape[1:])
                  for plane in (cache["k"][0], cache["v"][0]))
    del cache
    return (np.concatenate(probes, axis=1), np.asarray(where),
            np.stack(logits, axis=1), np.stack(chosen, axis=1), states,
            tails, stray, pages)


def _rms_rel(pairs) -> float:
    """rms of the differences over the rms of the references, pooled."""
    import numpy as np

    d2 = sum(float(np.sum((np.asarray(a, np.float64)
                           - np.asarray(b, np.float64)) ** 2))
             for a, b in pairs)
    r2 = sum(float(np.sum(np.asarray(b, np.float64) ** 2)) for _, b in pairs)
    return float(np.sqrt(d2 / r2))


def compare(engine, seed: int, B: int = 2, T: int = T_PROMPT,
            ref_params=None, n_decode: int = N_DECODE, chunk: int = CHUNK,
            variant: str = "") -> dict:
    import numpy as np

    from dynamo_tpu.engine import model as M

    from benchmarks.chip.reference import gaps
    from benchmarks.chip.shape import tokens_for

    cfg = engine.model_config
    if set(cfg.layer_types) != {LINEAR, FULL} or not cfg.gated_delta:
        raise ValueError("olmo_hybrid judges a table of gated-delta-rule "
                         f"and full_attention layers, not {cfg.layer_types}")
    bs = engine.config.block_size
    toks = np.asarray([tokens_for(seed, "ref", b, T, cfg.vocab_size)
                       for b in range(B)], np.int32)
    probes, where, decoded, chosen, states, tails, stray, pages = served(
        engine, toks, chunk, n_decode + 1)
    params = engine.params if ref_params is None else ref_params
    # the pages compared: the one the second chunk starts in, and the last
    # whole page of decoded tokens (the last fed token is T + n_decode - 1)
    blocks = sorted({min(chunk, T - 1) // bs, (T + n_decode) // bs - 1})
    ref_probes, ref_decoded = [], []
    n_lin = cfg.layer_types.count(LINEAR)
    state_pairs = [[] for _ in range(n_lin)]
    tail_pairs = [[] for _ in range(n_lin)]
    kv_pairs = []
    for b in range(B):
        full = np.concatenate([toks[b], chosen[b, :n_decode]])
        hidden, ref_states, ref_tails, (ref_k, ref_v) = reference_hidden(
            cfg, params, full, variant)
        ref_probes.append(np.asarray(
            head_logits(cfg, params, hidden[where]), np.float32))
        ref_decoded.append(np.asarray(
            head_logits(cfg, params, hidden[T - 1:T + n_decode]), np.float32))
        for l in range(n_lin):
            state_pairs[l].append((states[l, b], ref_states[l]))
            tail_pairs[l].append((tails[l, b], ref_tails[l]))
        for plane, ref in zip(pages, (ref_k, ref_v)):
            for j in blocks:          # [KV, bs, hd] against [bs, KV, hd]
                kv_pairs.append((plane[b, j], np.swapaxes(np.asarray(
                    ref[j * bs:(j + 1) * bs]), 0, 1)))
    by_layer = [_rms_rel(p) for p in state_pairs]
    out = {"B": B, "T": T, "n_decode": n_decode, "chunk": chunk,
           "probes": [int(w) for w in where], "rel_tol": REL_TOL,
           "variant": variant,
           "decode_attention": dict(M.ATTENTION_TRACES.get("decode", {})),
           "state": {"rms_rel_by_layer": by_layer,
                     "rms_rel_first": by_layer[0],
                     "rms_rel_max": max(by_layer), "stray_max": stray},
           "conv": {"rms_rel_max": max(_rms_rel(p) for p in tail_pairs)},
           "kv": {"rms_rel": _rms_rel(kv_pairs), "blocks": blocks}}
    ok = stray == 0.0 and bool(np.all(np.isfinite(states)))
    for name, s, r in (("prefill", probes, np.stack(ref_probes)),
                       ("decode", decoded[:, 1:],
                        np.stack(ref_decoded)[:, 1:])):
        out[name] = g = gaps(s, r)
        ok = (ok and g["finite"]
              and g["max_abs_diff"] <= REL_TOL * g["max_abs_ref"])
    p, d = out["prefill"], out["decode"]
    n_p, n_d = probes.size, decoded[:, 1:].size
    out["both"] = {"rms_rel": float(np.sqrt(
        (n_p * p["rms_diff"] ** 2 + n_d * d["rms_diff"] ** 2)
        / (n_p * p["rms_ref"] ** 2 + n_d * d["rms_ref"] ** 2)))}
    out["ok"] = ok
    return out
