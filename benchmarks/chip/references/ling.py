"""Plain reference of a hybrid decoder as ``inclusionAI/Ling-3.0-flash``
publishes one (``model_type`` ``bailing_hybrid``; ``configs/ling-3.0-flash-
ep8.json`` names this module), on the share of it a configuration holds.

Every layer: ``h += Attn(rmsnorm(h)); h += FFN(rmsnorm(h))``; the kinds by
``layer_types``.

- ``linear_attention`` (Kimi Delta Attention, arXiv:2510.26692), ``H`` heads
  of ``dk = dv = head_dim``, ``x`` the normed input: ``q, k, v = silu(conv4(x
  Wq)), silu(conv4(x Wk)), silu(conv4(x Wv))``, the convolution causal and
  depthwise over time with ``short_conv_kernel_size`` taps, ``y_t = sum_j
  w[j] u_{t - 3 + j}`` (inputs before the sequence are zeros); ``q``, ``k``
  divided by their norm a head, ``q`` times ``dk^-0.5``; log decay a channel
  ``g = kda_lower_bound * sigmoid(exp(A_log[h]) * (x Wf + dt_bias))``;
  ``beta = sigmoid(x Wb)`` a head; a state ``S [dk, dv]`` a head from zeros,
  token by token: ``S <- Diag(exp(g_t)) S; S <- S + beta_t k_t (v_t - S^T
  k_t)^T; o_t = S^T q_t``; ``o`` RMS-normed over each head with a weight of
  ``dv``, times ``sigmoid(x Wg)`` a head, then ``Wo``.  No rope.
- ``mla_attention`` (DeepSeek-V2, arXiv:2405.04434): ``q = x Wq -> [H,
  qk_nope | qk_rope]``; ``c | k_pe = x Wdkv -> [kv_lora_rank | qk_rope]``;
  ``c <- rmsnorm(c)``; rope at ``rope_theta`` on interleaved pairs ``(2i, 2i
  + 1)`` of ``q_pe`` and of the one ``k_pe`` all heads share; ``k_nope | v =
  c Wukv -> [H, qk_nope | v_head_dim]``; scores ``(q_nope . k_nope + q_pe .
  k_pe) / sqrt(qk_nope + qk_rope)``, causal softmax, ``o = P v``, the same
  head-wise gate, ``Wo``.  Expanded: every position's keys and values are
  multiplied out (the served decode path runs the absorbed form).
- FFN "dense": SwiGLU of ``intermediate_size``.  "sparse": ``s = sigmoid(x
  Wr)`` in float32 over ALL routed experts; for the choice only ``s' = s +
  b``; a group (``n_group`` equal ranges) scores the sum of its two largest
  ``s'``; the ``topk_group`` best groups are kept; the ``num_experts_per_tok``
  largest ``s'`` inside them are chosen; weights the chosen ``s`` (without
  ``b``) over their sum, times ``routed_scaling_factor``; ``h += sum_{e
  chosen, e held} w_e swiglu_e(x) + swiglu_shared(x)``.  The experts held are
  shard ``expert_shard.index`` of ``expert_shard.of``; what the others would
  add is left out, as in the program.
- final norm, head over the rows of the vocabulary held.

What the published config leaves open is listed in the configuration's file
under ``assumed``; this module follows the same list.

Everything is float32 ``jax.numpy`` at ``highest`` matmul precision, one
sequence at a time, no cache, no chunks, no kernels, nothing of
``engine/model.py``, ``ops/`` or ``parallel/moe.py``: the recurrence is a
``lax.scan`` over the whole sequence, one token a step.  The helpers shared
with ``references/laguna.py`` (``rms_norm``, ``swiglu``, one routed expert
cast to float32 at a time, the head in column blocks) are that reference's.

It reads the engine's leaves by name (``model._init_table_small``):
``attn_norm mlp_norm [L, ...]``; ``wq wo w_attn_gate`` one stack a kind
under the kind's name; ``kda_wk kda_wv kda_wf kda_w_beta kda_conv kda_a_log
kda_dt_bias kda_o_norm`` one row a linear layer; ``mla_wdkv mla_kv_norm
mla_wukv`` one row an MLA layer; the FFN leaves as ``laguna`` reads them,
and ``router_bias`` one row a sparse layer.

``compare``: ``B`` = 2 seeded sequences of ``T`` = 1100 tokens are prefilled
through the engine's ``forward`` in chunks of 512 (the state crosses two
chunk boundaries; the last chunk, 76 tokens, is no multiple of the chunked
form's 64), 70 latent pages each and seats 3 and 1 of a state pool of four
(neither in order nor next to each other, so a state read from or written
to another seat shows); then ``N_DECODE`` = 128 tokens are decoded one at a
time through the decode path (the token recurrence, the absorbed latent
attention through the Pallas kernels where the engine runs them).
Compared against this forward's over prompt + the tokens the engine chose:

- the logits at each chunk's first ``HEAD`` = 4 positions and its last
  (0-3, 511, 512-515, 1023, 1024-1027, 1099): just behind a boundary nothing
  carries the past but the seat's state and the cache's pages;
- the logits of the 128 decode steps;
- the rows' seats read back after the last step, a linear layer each,
  against the state this module's recurrence ends with
  (``state.rms_rel_by_layer``), and the seats no row held, which must be
  the zeros they were made as (``state.stray_max``).

A top 8 of 512 is a discrete choice, so as in ``laguna`` the reference
computes each token with the experts the SERVED path chose (``variant``
"own_topk" leaves it to its own) and judges the choices apart:
``routing.flipped`` counts the (token, layer) whose served set is not the
reference's own choice on the same hidden state, ``routing.short_max`` how
far at most a served expert falls short: its biased score under the
reference's 8th inside the kept groups, as a share of it, or, where it lies
in a group the reference dropped, that group's score under the last group
kept.

Tolerances.  ``REL_TOL`` = 6% of the largest reference logit on each phase,
``laguna``'s for the same count of rounding sublayers; ``SHORT_TOL`` as
there.  The limits that refuse a lower precision or a part of the
mathematics left out are read on the chip and kept with their readings in
``limits/<configuration>.json``.  **The state's precision** is held by the
comparison itself, and that rests on what the decays are drawn as: with a
mean decay of 0.2 a token (this PR's first draw) a state forgot in two or
three tokens, and one kept in bfloat16 parted from no logit (2.08-2.40%
beside the sound 2.00-2.27%).  Drawn near 1 (``model._init_table_small``: a
memory of ten to a thousand tokens a channel) a state carries what 128
decode steps round into it: a bfloat16 pool reads ``both.rms_rel``
3.06-3.13% beside the sound 2.15-2.29% (its decode logits 3.13-3.20%; its
prefill probes as sound, since a chunk carries its state in float32), and
its first layer's state, whose inputs are an embedding's and read 0.39-0.40%
sound, 1.40%.  ``variant`` breaks THIS forward on purpose ("no_groups",
"no_bound"): a served path that follows the equations then reads as far
from it as a served path that left the part out would read from the sound
reference.
"""

from __future__ import annotations

import dataclasses
import functools

from benchmarks.chip.references.laguna import (_f32, _one_expert,
                                               head_logits, rms_norm, swiglu)

REL_TOL = 0.06
SHORT_TOL = 0.5
T_PROMPT = 1100      # 512 + 512 + 76
N_DECODE = 128
CHUNK = 512
HEAD = 4             # positions compared just behind a chunk boundary
TIE_GAP = 2.0 ** -8

LINEAR, LATENT = "linear_attention", "mla_attention"


def linear_attention(x, w, *, heads, hd, conv_taps, lower_bound, eps,
                     variant=""):
    """``x [T, D]`` plus one sequence's KDA layer, and the state ``[heads,
    dk, dv]`` its last token leaves; ``w`` the layer's float32 leaves."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    u = jnp.concatenate([h @ w["wq"], h @ w["kda_wk"], h @ w["kda_wv"]], -1)
    past = jnp.concatenate([jnp.zeros((conv_taps - 1, u.shape[1])), u], 0)
    y = sum(w["kda_conv"][j] * past[j:j + T] for j in range(conv_taps))
    y = jax.nn.silu(y).reshape(T, 3, heads, hd)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q, k, v = unit(y[:, 0]) * hd ** -0.5, unit(y[:, 1]), y[:, 2]
    raw = jnp.exp(w["kda_a_log"])[:, None] * (
        h @ w["kda_wf"] + w["kda_dt_bias"]).reshape(T, heads, hd)
    g = lower_bound * jax.nn.sigmoid(raw)
    if variant == "no_bound":          # the "safe gate" left out
        g = -jax.nn.softplus(raw)
    beta = jax.nn.sigmoid(h @ w["kda_w_beta"])                 # [T, heads]

    def token(S, t):                   # S [heads, dk, dv]
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S
        delta = v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + (b_t[:, None] * k_t)[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S, o = jax.lax.scan(token, jnp.zeros((heads, hd, hd)),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * w["kda_o_norm"]
    o = o * jax.nn.sigmoid(h @ w["w_attn_gate"])[:, :, None]
    return x + o.reshape(T, heads * hd) @ w["wo"], S


def latent_attention(x, w, *, heads, rank, nope, rot, vdim, theta, eps):
    """``x [T, D]`` plus one sequence's MLA layer, expanded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(T, heads, nope + rot)
    ckpe = h @ w["mla_wdkv"]
    c = rms_norm(ckpe[:, :rank], w["mla_kv_norm"], eps)
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                      # [T, rot/2]

    def turn(a):                       # [T, n, rot], pairs (2i, 2i + 1)
        even, odd = a[..., 0::2], a[..., 1::2]
        cs, sn = cos[:, None, :], sin[:, None, :]
        both = jnp.stack([even * cs - odd * sn, odd * cs + even * sn], -1)
        return both.reshape(a.shape)

    q_pe = turn(q[..., nope:])
    k_pe = turn(ckpe[:, None, rank:])[:, 0]                    # [T, rot]
    kv = (c @ w["mla_wukv"]).reshape(T, heads, nope + vdim)
    s = (jnp.einsum("qnd,knd->nqk", q[..., :nope], kv[..., :nope])
         + jnp.einsum("qnd,kd->nqk", q_pe, k_pe)) / jnp.sqrt(
             jnp.float32(nope + rot))
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("nqk,knd->qnd", a, kv[..., nope:])
    o = o * jax.nn.sigmoid(h @ w["w_attn_gate"])[:, :, None]
    return x + o.reshape(T, heads * vdim) @ w["wo"]


def router_weights(h, w_router, bias, *, top_k, n_group, topk_group, scale,
                   forced=None, variant=""):
    """``[T, E]`` weights: ``scale * s_e / sum_chosen s`` on the chosen
    experts, 0 elsewhere; the chosen are this router's own, or ``forced [T,
    top_k]`` (the served path's).  Also ``[T]`` each: the relative gap
    between the k-th and the next eligible biased score; whether the forced
    set differs from the own one; how far at most a forced expert falls
    short, as a share: its biased score under the own k-th or, where its
    group was dropped, its group's score under the last group kept."""
    import jax
    import jax.numpy as jnp

    T, E = h.shape[0], w_router.shape[1]
    s = jax.nn.sigmoid(h @ w_router)
    biased = s + bias
    pick = biased
    group_short = jnp.zeros_like(biased)
    if n_group and variant != "no_groups":
        per = biased.reshape(T, n_group, E // n_group)
        best2 = jnp.sum(jnp.sort(per, axis=-1)[..., -2:], axis=-1)
        cut = jnp.sort(best2, axis=-1)[:, -topk_group]
        kept = jnp.repeat(best2 >= cut[:, None], E // n_group, axis=1)
        pick = jnp.where(kept, biased, -jnp.inf)
        # an expert of a dropped group: how far its group lies under the
        # last group kept
        group_short = jnp.repeat(
            jnp.maximum(cut[:, None] - best2, 0.0) / jnp.abs(cut[:, None]),
            E // n_group, axis=1)
    ranked = jnp.sort(pick, axis=-1)
    kth, nxt = ranked[:, -top_k], ranked[:, -top_k - 1]
    own = pick >= kth[:, None]
    mask = own
    if forced is not None:
        mask = jnp.zeros_like(own).at[
            jnp.arange(T)[:, None], forced].set(True)
    chosen = jnp.where(mask, s, 0.0)
    chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    expert_short = jnp.maximum(kth[:, None] - biased, 0.0) / jnp.abs(
        kth[:, None])
    short = jnp.max(jnp.where(mask, jnp.where(jnp.isfinite(pick),
                                              expert_short, group_short),
                              0.0), axis=-1)
    return (chosen * scale, (kth - nxt) / jnp.abs(kth),
            jnp.any(mask != own, axis=-1), short)


def layer_weights(cfg, layers: dict, li: int) -> dict:
    """Layer ``li``'s small leaves in float32, found by this module's own
    reading of the published lists."""
    kind = cfg.layer_types[li]
    at = cfg.layer_types[:li].count(kind)
    w = {k: _f32(layers[k][li]) for k in ("attn_norm", "mlp_norm")}
    for k in ("wq", "wo", "w_attn_gate"):
        w[k] = _f32(layers[k][kind][at])
    prefix = "kda_" if kind == LINEAR else "mla_"
    for k in layers:
        if k.startswith(prefix):
            w[k] = _f32(layers[k][at])
    ffn = cfg.mlp_layer_types[li]
    fat = cfg.mlp_layer_types[:li].count(ffn)
    names = (("w_gate", "w_up", "w_down") if ffn == "dense" else
             ("w_router", "router_bias", "shared_gate", "shared_up",
              "shared_down"))
    for k in names:
        w[k] = _f32(layers[k][fat])
    return w


@functools.lru_cache(maxsize=None)
def _attention_jit(kind: str, **kw):
    import jax

    fn = linear_attention if kind == LINEAR else latent_attention
    return jax.jit(functools.partial(fn, **kw))


def reference_hidden(cfg, params, tokens, variant: str = "", choices=None):
    """Final-normed float32 hidden states ``[T, D]`` of ONE sequence, of
    each sparse layer the routing's ``(gap, flipped, short)``, and of each
    linear layer the state its last token leaves.  ``choices [sparse
    layers, T, k]`` forces each token's experts; None: the reference's
    own."""
    import jax
    import jax.numpy as jnp

    layers = params["layers"]
    shard = dict(cfg.expert_shard or (("index", 0), ("of", 1)))
    n_held = cfg.num_routed_experts // shard["of"]
    first = shard["index"] * n_held
    rope = {k: dict(v) for k, v in dict(cfg.rope_parameters).items()}
    one_expert = _one_expert()
    routing, states = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        for li in range(cfg.num_layers):
            w = layer_weights(cfg, layers, li)
            kind, heads = cfg.layer_types[li], cfg.num_heads_per_layer[li]
            if kind == LINEAR:
                x, S = _attention_jit(
                    kind, heads=heads, hd=cfg.head_dim,
                    conv_taps=cfg.short_conv_kernel_size,
                    lower_bound=float(cfg.kda_lower_bound),
                    eps=cfg.rms_norm_eps, variant=variant)(x, w)
                states.append(S)
            else:
                x = _attention_jit(
                    kind, heads=heads, rank=cfg.kv_lora_rank,
                    nope=cfg.qk_nope_head_dim, rot=cfg.qk_rope_head_dim,
                    vdim=cfg.v_head_dim,
                    theta=float(rope[kind]["rope_theta"]),
                    eps=cfg.rms_norm_eps)(x, w)
            h = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
            if cfg.mlp_layer_types[li] == "dense":
                x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
                continue
            fat = cfg.mlp_layer_types[:li].count("sparse")
            weight, *stats = router_weights(
                h, w["w_router"], w["router_bias"],
                top_k=cfg.num_experts_per_token, n_group=cfg.n_group,
                topk_group=cfg.topk_group,
                scale=cfg.moe_routed_scaling_factor, variant=variant,
                forced=None if choices is None else jnp.asarray(choices[fat]))
            routing.append(stats)
            weight = weight[:, first:first + n_held]
            y = swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
            for e in range(n_held):       # one expert in float32 at a time
                y = y + one_expert(
                    h, weight[:, e], layers["expert_gate"][fat][e],
                    layers["expert_up"][fat][e],
                    layers["expert_down"][fat][e])
            x = x + y
        x = rms_norm(x, _f32(params["final_norm"]), cfg.rms_norm_eps)
    return x, routing, states


@functools.lru_cache(maxsize=4)
def served_step(cfg, eng, mesh, at: tuple):
    """The program's ``forward`` + head on one chunk, the logits taken at
    the chunk's positions ``at``; jitted once for a configuration."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import model as M

    def run(params, cache, tok, p, tb, seats):
        experts = []
        cache, h = M.forward(cfg, eng, params, cache, tok, p, tb, mesh=mesh,
                             moe_choices=experts, seats=seats)
        return (cache, M.logits_fn(cfg, params, h[:, jnp.asarray(at)]),
                jnp.stack(experts))

    return jax.jit(run, donate_argnums=(1,))


def chunk_probes(n: int) -> tuple:
    """The positions of a chunk of ``n`` tokens whose logits are compared:
    the first ``HEAD`` (just behind the boundary, where nothing but the
    seat's state and the cache's pages carries the past) and the last."""
    return tuple(range(min(HEAD, n - 1))) + (n - 1,)


def served(engine, toks, chunk: int, n_decode: int):
    """What the program serves for ``toks [B, T]``: the prompt prefilled in
    chunks of ``chunk`` through ``forward``, a paged latent cache and a
    state pool of ``B + 2`` seats of which the rows hold ``B + 1, B - 1,
    ..`` (neither in order nor next to each other), then ``n_decode``
    greedy tokens decoded one at a time.  Returns the logits at every
    chunk's probes ``[B, P, V]`` with their positions ``[P]``, the decode
    logits ``[B, n_decode, V]``, the tokens chosen ``[B, n_decode]``, the
    experts every fed token chose in every sparse layer ``[sparse layers, B,
    T + n_decode - 1, k]``, each linear layer's states of the rows' seats
    after the last fed token ``[linear layers, B, H, dk, dv]`` float32, and
    the largest magnitude left in a seat no row held."""
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

    probes, where, routed = [], [], []
    for t0 in range(0, T, chunk):
        t1 = min(t0 + chunk, T)
        at = chunk_probes(t1 - t0)
        pos = np.tile(np.arange(t0, t1, dtype=np.int32), (B, 1))
        cache, lg, ex = served_step(cfg, eng, mesh, at)(
            engine.params, cache, toks[:, t0:t1], pos, tables, seats)
        probes.append(np.asarray(lg, np.float32))
        where.extend(t0 + a for a in at)
        routed.append(np.asarray(ex))
    step = served_step(cfg, eng, mesh, (0,))
    logits, chosen = [probes[-1][:, -1]], []
    for k in range(n_decode):
        nxt = np.argmax(logits[-1], -1).astype(np.int32)
        chosen.append(nxt)
        if k == n_decode - 1:
            break
        cache, lg, ex = step(engine.params, cache, nxt[:, None],
                             np.full((B, 1), T + k, np.int32), tables, seats)
        logits.append(np.asarray(lg, np.float32)[:, 0])
        routed.append(np.asarray(ex))
    states = np.stack([np.asarray(pool[seats], np.float32)
                       for pool in cache["state"]])
    others = np.setdiff1d(np.arange(eng.max_num_seqs + 1), seats)
    stray = max(float(jnp.max(jnp.abs(pool[others].astype(jnp.float32))))
                for pool in cache["state"] + cache["conv"])
    del cache
    return (np.concatenate(probes, axis=1), np.asarray(where),
            np.stack(logits, axis=1), np.stack(chosen, axis=1),
            np.concatenate(routed, axis=2), states, stray)


def compare(engine, seed: int, B: int = 2, T: int = T_PROMPT,
            ref_params=None, n_decode: int = N_DECODE, chunk: int = CHUNK,
            variant: str = "") -> dict:
    import numpy as np

    from dynamo_tpu.engine import model as M

    from benchmarks.chip.reference import gaps
    from benchmarks.chip.shape import tokens_for

    cfg = engine.model_config
    if set(cfg.layer_types) != {LINEAR, LATENT}:
        raise ValueError("ling judges a table of linear_attention and "
                         f"mla_attention layers, not {cfg.layer_types}")
    toks = np.asarray([tokens_for(seed, "ref", b, T, cfg.vocab_size)
                       for b in range(B)], np.int32)
    probes, where, decoded, chosen, experts, states, stray = served(
        engine, toks, chunk, n_decode + 1)
    params = engine.params if ref_params is None else ref_params
    ref_probes, ref_decoded, state_gap = [], [], []
    route = {"token_layers": 0, "near_ties": 0, "flipped": 0,
             "short_max": 0.0, "tie_gap": TIE_GAP}
    for b in range(B):
        full = np.concatenate([toks[b], chosen[b, :n_decode]])
        hidden, routing, ref_states = reference_hidden(
            cfg, params, full, variant,
            choices=None if variant == "own_topk" else experts[:, b])
        ref_probes.append(np.asarray(
            head_logits(cfg, params, hidden[where]), np.float32))
        ref_decoded.append(np.asarray(
            head_logits(cfg, params, hidden[T - 1:T + n_decode]), np.float32))
        state_gap.append([(float(np.sum((states[l, b] - np.asarray(S)) ** 2)),
                           float(np.sum(np.asarray(S) ** 2)))
                          for l, S in enumerate(ref_states)])
        for gap, flipped, short in routing:     # every position
            route["token_layers"] += int(gap.size)
            route["near_ties"] += int((np.asarray(gap) < TIE_GAP).sum())
            route["flipped"] += int(np.asarray(flipped).sum())
            route["short_max"] = max(route["short_max"],
                                     float(np.asarray(short).max()))
    route["flipped_share"] = route["flipped"] / max(1, route["token_layers"])
    # a layer's states of both sequences against the reference's
    diff2, ref2 = np.sum(np.asarray(state_gap), axis=0).T
    by_layer = [float(x) for x in np.sqrt(diff2 / ref2)]
    out = {"B": B, "T": T, "n_decode": n_decode, "chunk": chunk,
           "probes": [int(w) for w in where],
           "rel_tol": REL_TOL, "short_tol": SHORT_TOL, "variant": variant,
           "decode_attention": dict(M.ATTENTION_TRACES.get("decode", {})),
           "routing": route,
           "state": {"rms_rel_by_layer": by_layer,
                     "rms_rel_first": by_layer[0],
                     "rms_rel_max": max(by_layer), "stray_max": stray}}
    ok = stray == 0.0 and bool(np.all(np.isfinite(states)))
    for name, s, r in (("prefill", probes, np.stack(ref_probes)),
                       ("decode", decoded[:, 1:],
                        np.stack(ref_decoded)[:, 1:])):
        out[name] = g = gaps(s, r)
        ok = (ok and g["finite"]
              and g["max_abs_diff"] <= REL_TOL * g["max_abs_ref"])
    ok = ok and route["short_max"] <= SHORT_TOL
    p, d = out["prefill"], out["decode"]
    n_p, n_d = probes.size, decoded[:, 1:].size
    out["both"] = {"rms_rel": float(np.sqrt(
        (n_p * p["rms_diff"] ** 2 + n_d * d["rms_diff"] ** 2)
        / (n_p * p["rms_ref"] ** 2 + n_d * d["rms_ref"] ** 2)))}
    out["ok"] = ok
    return out
