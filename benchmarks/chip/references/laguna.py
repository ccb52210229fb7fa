"""Plain reference of a decoder with a table of layer kinds, as
``poolside/Laguna-S-2.1`` publishes one (``configs/laguna-s-2.1-ep2.json``
names this module), on the share of it that a configuration holds.

Layer ``l`` (``h`` the residual stream, ``eps`` ``rms_norm_eps``, ``hd``
``head_dim``, ``KV`` ``num_key_value_heads``):

- attention: ``x = rmsnorm(h)``; ``q = x Wq`` with ``H =
  num_attention_heads_per_layer[l]`` heads, ``k``, ``v`` with ``KV``; rope on
  ``q`` and ``k`` by ``rope_parameters[layer_types[l]]``: plain rope at
  ``rope_theta`` on the leading ``partial_rotary_factor`` of each head
  (rotate-half within that part, the rest passes through), and for
  ``rope_type`` "yarn" the inverse frequencies blended per dim between
  ``1/theta^(2i/d)`` and that over ``factor`` by the linear ramp between the
  correction dims of ``beta_fast`` and ``beta_slow``, ``cos`` and ``sin``
  times ``attention_factor``; scores ``q k^T / sqrt(hd)`` within groups of
  ``H / KV`` query heads; mask causal and, in ``sliding_attention`` layers,
  ``i - j < sliding_window``; ``o = softmax(scores) v``; gate (``gating``
  "per-head"): ``o[:, n] *= sigmoid(x Wg)[:, n]``; ``h += concat(o) Wo``.
- FFN, ``mlp_layer_types[l]`` "dense": ``h += (silu(x Wgate) * (x Wup))
  Wdown`` on ``x = rmsnorm(h)``.
- FFN, "sparse": ``s = softmax(x Wr)`` over ALL routed experts in float32;
  the ``num_experts_per_tok`` largest; ``w_e = moe_routed_scaling_factor *
  s_e / sum_top s`` (``norm_topk_prob``); ``h += sum_{e in top, e held} w_e
  swiglu_e(x) + swiglu_shared(x)``.  Every token keeps all its experts.  The
  experts held are shard ``expert_shard.index`` of ``expert_shard.of``; what
  the others would add is left out, as in the program: ``w_e`` is normalised
  over the chosen of all routed experts, never over the ones held.
- final norm, head over the rows of the vocabulary held.

Assumed, where the published config is silent (the configuration's file lists
the same under ``assumed``): the router scores by softmax; the gate is a
sigmoid of the layer's normed input, one scalar a query head, on the
attention output before ``Wo``; no q / k norm and no gate on the shared
expert.

Everything is float32 ``jax.numpy`` at ``highest`` matmul precision: no
kernel, no cache, no dispatch, nothing of ``engine/model.py`` or
``parallel/moe.py``.  Every routed expert held runs on every token and is
weighted 0 where not chosen, ONE expert cast to float32 at a time (a layer's
128 experts in float32 are 4.8 GB beside an engine's resident weights); the
head is applied to the compared positions only, in column blocks.

It reads the engine's leaves by name (``model._init_table_params``):
``attn_norm mlp_norm wk wv [L, ...]``; ``wq wo w_attn_gate`` one stack an
attention kind under the kind's name; ``w_gate w_up w_down`` one row a dense
layer; ``w_router shared_gate shared_up shared_down`` one row a sparse
layer; ``expert_gate expert_up expert_down`` a list of ``[Eh, in, out]`` a
sparse layer.

``compare``: ``B`` = 2 seeded sequences of ``T`` = 1088 tokens (two windows
of 512 and a bit, 68 pages of 16) are prefilled through the engine's
``forward`` in chunks of 512 into a paged cache (the chunked path: chunks 2
and 3 attend pages written by earlier ones, and every query of them lies
past the window), then ``N_DECODE`` = 8 tokens are decoded one at a time
through the decode attention path (the Pallas kernel, whose walk of a
sliding layer starts 512 keys back, at page 36 of 68).  Compared: the
logits at the last prefill position and at each decode step, against this
forward's over the whole sequence of prompt + the tokens the engine chose.
Both mechanisms that act past 512 positions are compared past 512.

The top ``k`` of the router is a discrete choice.  With seeded random
weights the 10th and 11th of 256 scores lie about 5% apart, and a served
bfloat16 hidden state that differs from float32 by 1% swaps them on a fair
share of the tokens; a token that computes another expert of ten is no
rounding of the same result, and its difference (read on the chip: 8-18% of
the logits' rms, ``variant`` "own_topk") would drown every control.  So the
comparison is in two parts.  (1) The reference computes each token with the
experts the SERVED path chose for it (``forward`` hands them out:
``moe_choices``), with its own float32 scores as their weights: what is
left between the two logits is arithmetic.  (2) The choices themselves are
judged against the reference's own scores on the same hidden state:
``routing.flipped`` counts the (token, layer) whose served set is not the
reference's top ``k``, over every position of both sequences, and
``routing.short_max`` is how far, at most, a served expert's score lies
under the reference's k-th largest, as a share of it.  A router that picks
by anything but these scores, or scores in a precision that reorders them,
reads there.

Tolerances.  ``REL_TOL`` = 6% of the largest reference logit on each phase
(the dense reference's 3% doubled for twice the sublayers that round: gate
and experts; it refuses a lost layer, a wrong mask or rope, a dropped
token), and ``SHORT_TOL``: no served expert may score more than that share
under the reference's k-th.  The limits that refuse a lower precision or a
broken routing weight are read on the chip for the configuration and kept
in ``limits/<configuration>.json`` with their readings (``both.rms_rel``,
``routing.short_max``): an int8 cache, a window ignored in decode, the scale
dropped, weights renormalised over the held experts, a bfloat16 router.
``routing.near_ties`` counts the (token, layer) of the reference whose 10th
and 11th score lie within ``TIE_GAP`` = 2^-8 of each other, the rounding of
one bfloat16 value.
"""

from __future__ import annotations

import dataclasses
import functools

REL_TOL = 0.06       # max |system - reference| over max |reference|, a phase
SHORT_TOL = 0.5      # a served expert's score under the reference's k-th
T_PROMPT = 1088      # > 2 windows of 512; 68 pages of 16
N_DECODE = 8
CHUNK = 512
TIE_GAP = 2.0 ** -8


def rope_tables(rope: dict, hd: int, pos):
    """cos, sin ``[T, rot/2]`` and the rotary width of one kind's rope."""
    import jax.numpy as jnp
    import numpy as np

    theta = float(rope["rope_theta"])
    rot = int(round(hd * float(rope.get("partial_rotary_factor", 1))))
    i = np.arange(0, rot, 2, dtype=np.float64)
    inv = theta ** (-i / rot)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
        scale = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def dim_of(turns):      # the dim that makes `turns` turns in `orig`
            return rot * np.log(orig / (turns * 2 * np.pi)) / (
                2 * np.log(theta))

        low = max(np.floor(dim_of(float(rope["beta_fast"]))), 0.0)
        high = min(np.ceil(dim_of(float(rope["beta_slow"]))), rot - 1.0)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
        inv = (inv / scale) * ramp + inv * (1.0 - ramp)
        factor = float(rope.get("attention_factor")
                       or 0.1 * np.log(scale) + 1.0)
    ang = jnp.asarray(pos, jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor, rot


def rms_norm(v, g, eps):
    import jax
    import jax.numpy as jnp

    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g


def attention(x, w, *, heads, kv, hd, rope, window, eps):
    """``x [T, D]`` plus one sequence's gated attention; ``w``: float32
    ``attn_norm wq wk wv wo`` and ``w_attn_gate`` or None."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    cos, sin, rot = rope_tables(rope, hd, jnp.arange(T))

    def turn(v):                                   # [T, n, hd]
        a, b, rest = v[..., :rot // 2], v[..., rot // 2:rot], v[..., rot:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)

    q = turn((h @ w["wq"]).reshape(T, heads, hd))
    k = turn((h @ w["wk"]).reshape(T, kv, hd))
    v = (h @ w["wv"]).reshape(T, kv, hd)
    g = heads // kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    if window:
        mask = mask & (i - j < window)
    outs = []
    for n0 in range(0, heads, 8):                  # 8 heads of scores a time
        s = jnp.einsum("qnd,knd->nqk", q[:, n0:n0 + 8], k[:, n0:n0 + 8]
                       ) / jnp.sqrt(jnp.float32(hd))
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("nqk,knd->qnd", a, v[:, n0:n0 + 8]))
    o = jnp.concatenate(outs, axis=1)              # [T, heads, hd]
    if w.get("w_attn_gate") is not None:
        o = o * jax.nn.sigmoid(h @ w["w_attn_gate"])[:, :, None]
    return x + o.reshape(T, heads * hd) @ w["wo"]


def swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def router_weights(h, w_router, *, top_k, renormalise, scale, forced=None):
    """``[T, E]`` weights: ``scale * s_e / sum_chosen s`` on the chosen
    experts of ``softmax(h Wr)``, 0 elsewhere.  The chosen are the ``top_k``
    largest, or ``forced [T, top_k]`` where given (the served path's own
    choices).  Also, ``[T]`` each: the relative gap between the k-th and the
    next score; whether the forced set differs from the ``top_k`` largest;
    and how far the least forced score lies under the k-th largest, as a
    share of it (0 where the sets are equal)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(h @ w_router, axis=-1)
    ranked = jnp.sort(s, axis=-1)
    kth, nxt = ranked[:, -top_k], ranked[:, -top_k - 1]
    own = s >= kth[:, None]
    mask = own
    if forced is not None:
        mask = jnp.zeros_like(own).at[
            jnp.arange(s.shape[0])[:, None], forced].set(True)
    chosen = jnp.where(mask, s, 0.0)
    least = jnp.min(jnp.where(mask, s, jnp.inf), axis=-1)
    if renormalise:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return (chosen * scale, (kth - nxt) / kth, jnp.any(mask != own, axis=-1),
            jnp.maximum(kth - least, 0.0) / kth)


@functools.lru_cache(maxsize=None)
def _one_expert():
    """One routed expert on every token, weighted: compiled once."""
    import jax

    return jax.jit(lambda h, wt, g, u, d: wt[:, None] * swiglu(
        h, _f32(g), _f32(u), _f32(d)))


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a).astype(jnp.float32)


def layer_weights(cfg, layers: dict, li: int) -> dict:
    """Layer ``li``'s small leaves in float32, found in the table's stacks
    by this module's own reading of the published lists."""
    kind = cfg.layer_types[li]
    at = cfg.layer_types[:li].count(kind)
    w = {k: _f32(layers[k][li]) for k in ("attn_norm", "mlp_norm", "wk", "wv")}
    w["wq"] = _f32(layers["wq"][kind][at])
    w["wo"] = _f32(layers["wo"][kind][at])
    w["w_attn_gate"] = (_f32(layers["w_attn_gate"][kind][at])
                        if cfg.attn_gate else None)
    ffn = cfg.mlp_layer_types[li]
    fat = cfg.mlp_layer_types[:li].count(ffn)
    names = (("w_gate", "w_up", "w_down") if ffn == "dense" else
             ("w_router", "shared_gate", "shared_up", "shared_down"))
    for k in names:
        w[k] = _f32(layers[k][fat])
    return w


def reference_hidden(cfg, params, tokens, variant: str = "", choices=None):
    """Final-normed float32 hidden states ``[T, D]`` of ONE sequence
    ``tokens [T]``, and of each sparse layer the routing's ``(gap, flipped,
    short)``, ``[T]`` each (:func:`router_weights`).  ``choices`` ``[sparse
    layers, T, k]`` forces each token's experts (the served path's own
    choices); None: the reference's own ``top_k``.

    ``variant`` breaks the mathematics on purpose, for the controls a limit
    has to refuse (``limits/<configuration>.json``): "no_scale" drops
    ``moe_routed_scaling_factor``, "renorm_held" normalises the weights over
    the chosen experts that are held."""
    import jax
    import jax.numpy as jnp

    layers = params["layers"]
    rope = {k: dict(v) for k, v in dict(cfg.rope_parameters).items()}
    shard = dict(cfg.expert_shard or (("index", 0), ("of", 1)))
    n_held = cfg.num_routed_experts // shard["of"]
    first = shard["index"] * n_held
    one_expert = _one_expert()
    routing = []
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        for li in range(cfg.num_layers):
            w = layer_weights(cfg, layers, li)
            kind = cfg.layer_types[li]
            x = attention(
                x, w, heads=cfg.num_heads_per_layer[li],
                kv=cfg.num_kv_heads, hd=cfg.head_dim, rope=rope[kind],
                window=(cfg.sliding_window if kind == "sliding_attention"
                        else 0), eps=cfg.rms_norm_eps)
            h = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
            if cfg.mlp_layer_types[li] == "dense":
                x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
                continue
            fat = cfg.mlp_layer_types[:li].count("sparse")
            weight, *stats = router_weights(
                h, w["w_router"], top_k=cfg.num_experts_per_token,
                renormalise=cfg.norm_topk_prob,
                scale=(1.0 if variant == "no_scale"
                       else cfg.moe_routed_scaling_factor),
                forced=None if choices is None else jnp.asarray(choices[fat]))
            routing.append(stats)
            weight = weight[:, first:first + n_held]
            if variant == "renorm_held":
                weight = (weight / jnp.maximum(
                    jnp.sum(weight, -1, keepdims=True), 1e-30)
                    * cfg.moe_routed_scaling_factor)
            y = swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
            for e in range(n_held):       # one expert in float32 at a time
                y = y + one_expert(
                    h, weight[:, e], layers["expert_gate"][fat][e],
                    layers["expert_up"][fat][e],
                    layers["expert_down"][fat][e])
            x = x + y
        x = rms_norm(x, _f32(params["final_norm"]), cfg.rms_norm_eps)
    return x, routing


def head_logits(cfg, params, hidden):
    """float32 logits ``[n, V]`` of ``hidden [n, D]``, the head in blocks."""
    import jax
    import jax.numpy as jnp

    head = (params["embed"].T if cfg.tie_word_embeddings
            else params["lm_head"])
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [hidden @ _f32(head[:, c:c + 8192])
             for c in range(0, head.shape[1], 8192)], axis=-1)


@functools.lru_cache(maxsize=4)
def served_step(cfg, eng, mesh):
    """The program's ``forward`` + head on one chunk, jitted once for a
    configuration (a seed after the first compiles nothing; a caller that
    patches the program clears this cache)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import model as M

    def run(params, cache, tok, p, tb):
        experts = []
        cache, h = M.forward(cfg, eng, params, cache, tok, p, tb, mesh=mesh,
                             moe_choices=experts)
        return cache, M.logits_fn(cfg, params, h[:, -1]), jnp.stack(experts)

    return jax.jit(run, donate_argnums=(1,))


def served(engine, toks, chunk: int, n_decode: int):
    """What the program serves for ``toks [B, T]``: the prompt prefilled in
    chunks of ``chunk`` through ``forward`` and a paged cache, then
    ``n_decode`` greedy tokens decoded one at a time through the decode
    attention path.  Returns the logits ``[B, n_decode, V]`` (last prompt
    position, then each decode step), the tokens chosen ``[B, n_decode]``
    and the experts every fed token chose in every sparse layer,
    ``[sparse layers, B, T + n_decode - 1, k]``."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import model as M

    cfg, mesh = engine.model_config, engine.mesh
    B, T = toks.shape
    bs = engine.config.block_size
    nb = -(-(T + n_decode) // bs)
    eng = dataclasses.replace(engine.config, num_blocks=B * nb + 1)
    cache = M.init_cache(cfg, eng)
    W = max(eng.max_blocks_per_seq, nb)
    tables = np.zeros((B, W), np.int32)
    for b in range(B):
        tables[b, :nb] = 1 + b * nb + np.arange(nb)

    fn = served_step(cfg, eng, mesh)
    lg, routed = None, []
    for t0 in range(0, T, chunk):
        t1 = min(t0 + chunk, T)
        pos = np.tile(np.arange(t0, t1, dtype=np.int32), (B, 1))
        cache, lg, ex = fn(engine.params, cache, toks[:, t0:t1], pos, tables)
        routed.append(np.asarray(ex))
    logits, chosen = [lg], []
    for k in range(n_decode):
        nxt = np.asarray(jnp.argmax(logits[-1], -1)).astype(np.int32)
        chosen.append(nxt)
        if k == n_decode - 1:
            break
        cache, lg, ex = fn(engine.params, cache, nxt[:, None],
                           np.full((B, 1), T + k, np.int32), tables)
        logits.append(lg)
        routed.append(np.asarray(ex))
    del cache
    return (np.stack([np.asarray(x, np.float32) for x in logits], axis=1),
            np.stack(chosen, axis=1), np.concatenate(routed, axis=2))


def compare(engine, seed: int, B: int = 2, T: int = T_PROMPT,
            ref_params=None, n_decode: int = N_DECODE, chunk: int = CHUNK,
            variant: str = "") -> dict:
    import numpy as np

    from dynamo_tpu.engine import model as M

    from benchmarks.chip.reference import gaps
    from benchmarks.chip.shape import tokens_for

    cfg = engine.model_config
    if not cfg.layer_types:
        raise ValueError("laguna judges a configuration with a table of "
                         "layer kinds; this one has none")
    window = cfg.sliding_window
    if window and T <= 2 * window:
        raise ValueError(f"{T} prompt tokens do not reach past two windows "
                         f"of {window}")
    toks = np.asarray([tokens_for(seed, "ref", b, T, cfg.vocab_size)
                       for b in range(B)], np.int32)
    sysl, chosen, experts = served(engine, toks, chunk, n_decode + 1)
    params = engine.params if ref_params is None else ref_params
    refl = []
    route = {"token_layers": 0, "near_ties": 0, "flipped": 0,
             "short_max": 0.0, "tie_gap": TIE_GAP}
    for b in range(B):
        full = np.concatenate([toks[b], chosen[b, :n_decode]])
        hidden, routing = reference_hidden(
            cfg, params, full, variant,
            choices=None if variant == "own_topk" else experts[:, b])
        refl.append(np.asarray(
            head_logits(cfg, params, hidden[T - 1:T + n_decode]), np.float32))
        for gap, flipped, short in routing:     # every position, not only
            route["token_layers"] += int(gap.size)      # the compared ones
            route["near_ties"] += int((np.asarray(gap) < TIE_GAP).sum())
            route["flipped"] += int(np.asarray(flipped).sum())
            route["short_max"] = max(route["short_max"],
                                     float(np.asarray(short).max()))
    route["flipped_share"] = route["flipped"] / max(1, route["token_layers"])
    refl = np.stack(refl)                          # [B, 1 + n_decode, V]
    out = {"B": B, "T": T, "n_decode": n_decode, "chunk": chunk,
           "rel_tol": REL_TOL, "short_tol": SHORT_TOL, "variant": variant,
           "decode_attention": dict(M.ATTENTION_TRACES.get("decode", {})),
           "routing": route}
    ok = True
    for name, s, r in (("prefill", sysl[:, :1], refl[:, :1]),
                       ("decode", sysl[:, 1:], refl[:, 1:])):
        out[name] = g = gaps(s, r)
        ok = (ok and g["finite"]
              and g["max_abs_diff"] <= REL_TOL * g["max_abs_ref"])
    ok = ok and route["short_max"] <= SHORT_TOL
    p, d = out["prefill"], out["decode"]
    n_p, n_d = sysl[:, :1].size, sysl[:, 1:].size
    # rms over rms of every compared logit, prefill's and decode's pooled
    out["both"] = {"rms_rel": float(np.sqrt(
        (n_p * p["rms_diff"] ** 2 + n_d * d["rms_diff"] ** 2)
        / (n_p * p["rms_ref"] ** 2 + n_d * d["rms_ref"] ** 2)))}
    out["ok"] = ok
    return out
