"""Plain reference of the language model ``meituan-longcat/LongCat-Flash-Omni``
publishes (``configs/longcat-flash-omni-ep32.json`` names this module), on the
share of it a configuration holds.  The audio and vision encoders and the
codec decoder are not in the published ``config`` the catalog carries and are
out of scope: the backbone is judged on token ids.

One published layer is a DOUBLE layer and is written here as one (the
program runs it as two rows of its table).  With ``n_i`` / ``m_i`` its two
input / post-attention RMSNorms, ``A_i`` its two latent attentions, ``F_i``
its two dense SwiGLU FFNs of ``ffn_hidden_size`` and ``E`` its one expert
layer (shortcut-connected experts, arXiv:2509.01322):

    h1 = h  + A_0(n_0 h);   x = m_0 h1;   s = E(x)
    h2 = h1 + F_0(x)
    h3 = h2 + A_1(n_1 h2);  h4 = h3 + F_1(m_1 h3)
    out = h4 + s            # A_1 and F_1 never see s

- ``A`` (MLA with a q-LoRA, DeepSeek-V2, arXiv:2405.04434): ``cq = rmsnorm(x
  Wqa)``; ``q = (cq Wqb) * sqrt(hidden / q_lora_rank) -> [H, qk_nope |
  qk_rope]``; ``c | k_pe = x Wkva -> [kv_lora_rank | qk_rope]``; ``c =
  rmsnorm(c) * sqrt(hidden / kv_lora_rank)``; rope at ``rope_theta`` on
  interleaved pairs ``(2i, 2i + 1)`` of ``q_pe`` and of the one ``k_pe`` all
  heads share; ``k_nope | v = c Wkvb -> [H, qk_nope | v_head_dim]``; scores
  ``(q_nope . k_nope + q_pe . k_pe) / sqrt(qk_nope + qk_rope)``, causal
  softmax, ``o = P v``, ``Wo``.  No bias, no gate.  Expanded: every
  position's keys and values are multiplied out (the served decode path runs
  the absorbed form over a paged latent).
- ``E``: ``p = softmax(x Wr)`` in float32 over ALL router outputs, the
  ``num_routed_experts`` routed experts and behind them ``zero_expert_num``
  zero-compute ones; the choice is the top ``moe_topk`` of ``p + b`` (``b``
  for the choice alone); ``w_e = routed_scaling_factor * p_e``, NOT
  renormalised; ``E(x) = sum_{e chosen, e routed} w_e swiglu_e(x) + (sum_{e
  chosen, e zero-compute} w_e) x``.  The experts held are shard
  ``expert_shard.index`` of ``expert_shard.of`` of the routed ones; what the
  others would add is left out, as in the program; the identity part is
  every shard's alike and is computed in full.
- final norm, head over the rows of the vocabulary held.

What the published config leaves open is listed in the configuration's file
under ``assumed``; this module follows the same list.

Everything is float32 ``jax.numpy`` at ``highest`` matmul precision, one
sequence at a time, no cache, no chunks, no kernels, nothing of
``engine/model.py``, ``ops/`` or ``parallel/moe.py`` (the scores of a
sequence are made a block of heads at a time, ``lax.map``: 64 heads x 1228^2
float32 at once do not fit beside the served model).  The helpers shared
with ``references/laguna.py`` (``rms_norm``, ``swiglu``, one routed expert
cast to float32 at a time, the head in column blocks) are that reference's.

It reads the engine's leaves by name (``model._init_table_small``), the two
rows of double layer ``d`` being ``2d`` and ``2d + 1``: ``attn_norm mlp_norm
[rows, ...]`` (``n_0 m_0`` at ``2d``, ``n_1 m_1`` at ``2d + 1``); ``wq``
(``Wqb``) and ``wo`` under ``"mla_attention"``, ``mla_wqa mla_q_norm mla_wdkv
mla_kv_norm mla_wukv``, one row an attention; ``shared_gate shared_up
shared_down`` (``F_0``), ``w_router router_bias`` and the ``expert_*`` lists
one row a double layer; ``w_gate w_up w_down`` (``F_1``) likewise.

``compare``: ``B`` = 2 seeded sequences of ``T`` = 1100 tokens are prefilled
through the engine's ``forward`` in chunks of 512 (the latent pages cross two
chunk boundaries; the last chunk is 76 tokens), 73 latent pages each in 8
planes, then ``N_DECODE`` = 64 tokens are decoded one at a time through the
decode path (the absorbed latent attention through the Pallas kernel where
the engine runs it).  Compared against this forward's over prompt + the
tokens the engine chose: the logits at each chunk's first ``HEAD`` = 4
positions and its last (0-3, 511, 512-515, 1023, 1024-1027, 1099: just
behind a boundary nothing carries the past but the pages), and the logits of
the decode steps.

A top 12 of 768 is a discrete choice, so as in ``laguna`` and ``ling`` the
reference computes each token with the outputs the SERVED path chose
(``variant`` "own_topk" leaves it to its own) and judges the choices apart:
``routing.flipped`` counts the (token, layer) whose served set is not the
reference's own choice on the same hidden state, ``routing.short_max`` how
far at most a served output's biased score falls under the reference's own
12th, as a share of it, and ``routing.zero_strangers`` the served choices of
a zero-compute expert that the reference would not have made (an identity
chosen for a routed expert moves bytes and FLOPs as well as the sum).

**The router's precision** cannot be read off logits: scores rounded to
bfloat16 move a weight by 2^-9, far under what a bfloat16 residual stream
adds everywhere.  So the program's own router (``parallel.moe.route``, driven
as ``forward`` is) is run on THIS forward's float32 router inputs, and its
weights are held against this module's on the same input:
``router.weight_rel_max`` is the largest relative difference of a chosen
output's weight over the tokens whose two sets agree, ``router.set_mismatch``
the tokens whose sets differ.  Float32 against float32 they differ by
rounding in the last bits; scores kept in bfloat16 read 2^-9 to 2^-8.

Tolerances.  ``REL_TOL`` = 6% of the largest reference logit on each phase
(``laguna``'s and ``ling``'s, for as many rounding sublayers) and
``SHORT_TOL`` as there: what breaks the mathematics wholesale.  The limits
that refuse a lower precision or a part of the mathematics left out are read
on the chip and kept with their readings in ``limits/<configuration>.json``.
``variant`` breaks THIS forward on purpose ("no_identity": the zero-compute
experts add nothing; "no_scales": the two MLA scales left out; "early_join":
``s`` joins at ``h2``, before ``A_1``; "router_bf16": the scores rounded to
bfloat16 before the choice and the weights): a served path that follows the
equations then reads as far from it as a served path that left the part out
would read from the sound reference.
"""

from __future__ import annotations

import dataclasses
import functools

from benchmarks.chip.references.laguna import (_f32, _one_expert,
                                               head_logits, rms_norm, swiglu)

REL_TOL = 0.06
SHORT_TOL = 0.5
T_PROMPT = 1100      # 512 + 512 + 76
N_DECODE = 64
CHUNK = 512
HEAD = 4             # positions compared just behind a chunk boundary
TIE_GAP = 2.0 ** -8
HEAD_BLOCK = 16      # heads whose [T, T] scores are alive at once

LATENT = "mla_attention"
VARIANTS = ("", "own_topk", "no_identity", "no_scales", "early_join",
            "router_bf16")


def latent_attention(x, norm, w, *, heads, rank, q_rank, nope, rot, vdim,
                     theta, eps, scale_q, scale_kv):
    """``A(norm x)`` of one sequence ``x [T, D]``, expanded; ``w`` the
    attention's leaves as the engine keeps them (cast here)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    T, D = x.shape
    h = rms_norm(x, _f32(norm), eps)
    cq = rms_norm(h @ _f32(w["mla_wqa"]), _f32(w["mla_q_norm"]), eps)
    q = (cq @ _f32(w["wq"])).reshape(T, heads, nope + rot)
    ckpe = h @ _f32(w["mla_wdkv"])
    c = rms_norm(ckpe[:, :rank], _f32(w["mla_kv_norm"]), eps)
    if scale_q:
        q = q * np.sqrt(D / q_rank)
    if scale_kv:
        c = c * np.sqrt(D / rank)
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
    kv = (c @ _f32(w["mla_wukv"])).reshape(T, heads, nope + vdim)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def some_heads(part):              # a block of heads: [T, n, .] each
        qn, qp, kvn = part
        s = (jnp.einsum("qnd,knd->nqk", qn, kvn[..., :nope])
             + jnp.einsum("qnd,kd->nqk", qp, k_pe)) / jnp.sqrt(
                 jnp.float32(nope + rot))
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", a, kvn[..., nope:])

    n = min(HEAD_BLOCK, heads)

    def blocks(a):                     # [T, H, d] -> [H / n, T, n, d]
        return a.reshape(T, heads // n, n, a.shape[-1]).transpose(1, 0, 2, 3)

    o = jax.lax.map(some_heads, (blocks(q[..., :nope]), blocks(q_pe),
                                 blocks(kv)))
    o = o.transpose(1, 0, 2, 3).reshape(T, heads * vdim)
    return o @ _f32(w["wo"])


def router_weights(x, w_router, bias, *, top_k, scale, n_routed,
                   forced=None, variant=""):
    """``[T, E]`` weights over every router output: ``scale * p_e`` on the
    chosen, 0 elsewhere, not renormalised; the chosen are this router's own
    top ``top_k`` of ``p + bias``, or ``forced [T, top_k]`` (the served
    path's).  Also ``[T]`` each: the relative gap between the k-th and the
    next biased score; whether the forced set differs from the own one; how
    far at most a forced output's biased score falls under the own k-th, as
    a share of it; and how many forced outputs are zero-compute experts
    (index >= ``n_routed``) that the own choice does not hold."""
    import jax
    import jax.numpy as jnp

    T, E = x.shape[0], w_router.shape[1]
    p = jax.nn.softmax(x @ w_router, axis=-1)
    if variant == "router_bf16":       # scores kept in the lower precision
        p = p.astype(jnp.bfloat16).astype(jnp.float32)
    biased = p + bias
    ranked = jnp.sort(biased, axis=-1)
    kth, nxt = ranked[:, -top_k], ranked[:, -top_k - 1]
    own = biased >= kth[:, None]
    mask = own
    if forced is not None:
        mask = jnp.zeros_like(own).at[
            jnp.arange(T)[:, None], forced].set(True)
    short = jnp.max(jnp.where(
        mask, jnp.maximum(kth[:, None] - biased, 0.0) / jnp.abs(kth[:, None]),
        0.0), axis=-1)
    strangers = jnp.sum(mask & ~own & (jnp.arange(E)[None, :] >= n_routed),
                        axis=-1)
    return (jnp.where(mask, p, 0.0) * scale, (kth - nxt) / jnp.abs(kth),
            jnp.any(mask != own, axis=-1), short, strangers)


@functools.lru_cache(maxsize=None)
def _jitted(name: str, **kw):
    import jax

    fn = {"attention": latent_attention, "router": router_weights,
          "ffn": lambda h, g, u, d: swiglu(h, _f32(g), _f32(u), _f32(d))}
    return jax.jit(functools.partial(fn[name], **kw))


def attention_leaves(layers: dict, row: int) -> dict:
    """The leaves of the attention of table row ``row``."""
    w = {k: layers[k][LATENT][row] for k in ("wq", "wo")}
    for k in ("mla_wqa", "mla_q_norm", "mla_wdkv", "mla_kv_norm",
              "mla_wukv"):
        w[k] = layers[k][row]
    return w


def reference_hidden(cfg, params, tokens, variant: str = "", choices=None):
    """Final-normed float32 hidden states ``[T, D]`` of ONE sequence; of
    each double layer the routing's ``(gap, flipped, short, strangers)``;
    and of each the router's float32 input ``[T, D]`` with this module's own
    choice-free weights on it ``[T, E]`` (``scale * p``), for the check of
    the program's router.  ``choices [double layers, T, k]`` forces each
    token's router outputs; None: the reference's own."""
    import jax
    import jax.numpy as jnp

    layers = params["layers"]
    shard = dict(cfg.expert_shard or (("index", 0), ("of", 1)))
    n_routed = cfg.num_routed_experts
    n_held = n_routed // shard["of"]
    first = shard["index"] * n_held
    theta = float(dict(dict(cfg.rope_parameters)[LATENT])["rope_theta"])
    eps = cfg.rms_norm_eps
    scales = variant != "no_scales"
    attention = _jitted(
        "attention", heads=cfg.num_heads, rank=cfg.kv_lora_rank,
        q_rank=cfg.q_lora_rank, nope=cfg.qk_nope_head_dim,
        rot=cfg.qk_rope_head_dim, vdim=cfg.v_head_dim, theta=theta, eps=eps,
        scale_q=bool(cfg.mla_scale_q_lora and scales),
        scale_kv=bool(cfg.mla_scale_kv_lora and scales))
    router = _jitted("router", top_k=cfg.num_experts_per_token,
                     scale=float(cfg.moe_routed_scaling_factor),
                     n_routed=n_routed, variant=variant)
    ffn = _jitted("ffn")
    one_expert = _one_expert()
    routing, router_io = [], []
    with jax.default_matmul_precision("highest"):
        h = _f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        for d in range(cfg.num_layers // 2):
            a, b = 2 * d, 2 * d + 1
            h1 = h + attention(h, layers["attn_norm"][a],
                               attention_leaves(layers, a))
            x = rms_norm(h1, _f32(layers["mlp_norm"][a]), eps)
            # the expert layer, on the first FFN's input
            weight, *stats = router(
                x, _f32(layers["w_router"][d]), layers["router_bias"][d],
                forced=None if choices is None else jnp.asarray(choices[d]))
            routing.append(stats)
            router_io.append(x)
            s = jnp.zeros_like(x)
            if variant != "no_identity":
                s = jnp.sum(weight[:, n_routed:], axis=-1, keepdims=True) * x
            for e in range(n_held):       # one expert in float32 at a time
                s = s + one_expert(
                    x, weight[:, first + e], layers["expert_gate"][d][e],
                    layers["expert_up"][d][e], layers["expert_down"][d][e])
            h2 = h1 + ffn(x, layers["shared_gate"][d], layers["shared_up"][d],
                          layers["shared_down"][d])
            if variant == "early_join":   # before A_1, as a plain layer would
                h2 = h2 + s
            h3 = h2 + attention(h2, layers["attn_norm"][b],
                                attention_leaves(layers, b))
            h4 = h3 + ffn(rms_norm(h3, _f32(layers["mlp_norm"][b]), eps),
                          layers["w_gate"][d], layers["w_up"][d],
                          layers["w_down"][d])
            h = h4 if variant == "early_join" else h4 + s
        h = rms_norm(h, _f32(params["final_norm"]), eps)
    return h, routing, router_io


@functools.lru_cache(maxsize=None)
def _program_route(top_k: int, renormalise: bool, scale: float, score: str):
    """The program's router, jitted once for a configuration."""
    import jax

    from dynamo_tpu.parallel import moe

    return jax.jit(functools.partial(
        moe.route, top_k=top_k, renormalise=renormalise, scale=scale,
        score=score))


def program_router(cfg, params, router_io, variant: str = "") -> dict:
    """The program's own router (``parallel.moe.route``) on this forward's
    float32 router inputs, a double layer each, against this module's router
    on the same input: the largest relative difference of a chosen output's
    weight over the tokens whose sets agree, and the tokens whose sets do
    not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    k = cfg.num_experts_per_token
    served = _program_route(k, bool(cfg.norm_topk_prob),
                            float(cfg.moe_routed_scaling_factor),
                            cfg.score_function)
    own = _jitted("router", top_k=k,
                  scale=float(cfg.moe_routed_scaling_factor),
                  n_routed=cfg.num_routed_experts, variant=variant)
    rel, mismatch, tokens = 0.0, 0, 0
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        for d, x in enumerate(router_io):
            bias = layers["router_bias"][d]
            idx, w = served(x, layers["w_router"][d], bias=bias)
            weight = own(x, _f32(layers["w_router"][d]), bias)[0]
            mine = np.asarray(jnp.take_along_axis(weight, idx, axis=1))
            w = np.asarray(w)
            same = (mine > 0).all(axis=1)      # the served set is the own
            tokens += int(same.size)
            mismatch += int((~same).sum())
            if same.any():
                rel = max(rel, float(np.max(
                    np.abs(w[same] - mine[same]) / mine[same])))
    return {"weight_rel_max": rel, "set_mismatch": mismatch,
            "tokens": tokens}


@functools.lru_cache(maxsize=4)
def served_step(cfg, eng, mesh, at: tuple):
    """The program's ``forward`` + head on one chunk, the logits taken at
    the chunk's positions ``at``; jitted once for a configuration."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import model as M

    def run(params, cache, tok, p, tb):
        experts = []
        cache, h = M.forward(cfg, eng, params, cache, tok, p, tb, mesh=mesh,
                             moe_choices=experts)
        return (cache, M.logits_fn(cfg, params, h[:, jnp.asarray(at)]),
                jnp.stack(experts))

    return jax.jit(run, donate_argnums=(1,))


def chunk_probes(n: int) -> tuple:
    """The positions of a chunk of ``n`` tokens whose logits are compared:
    the first ``HEAD`` (just behind the boundary, where nothing but the
    cache's pages carries the past) and the last."""
    return tuple(range(min(HEAD, n - 1))) + (n - 1,)


def served(engine, toks, chunk: int, n_decode: int):
    """What the program serves for ``toks [B, T]``: the prompt prefilled in
    chunks of ``chunk`` through ``forward`` and a paged latent cache of its
    own, then ``n_decode`` greedy tokens decoded one at a time.  Returns the
    logits at every chunk's probes ``[B, P, V]`` with their positions
    ``[P]``, the decode logits ``[B, n_decode, V]``, the tokens chosen ``[B,
    n_decode]`` and the router outputs every fed token chose in every double
    layer ``[double layers, B, T + n_decode - 1, k]``."""
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

    probes, where, routed = [], [], []
    for t0 in range(0, T, chunk):
        t1 = min(t0 + chunk, T)
        at = chunk_probes(t1 - t0)
        pos = np.tile(np.arange(t0, t1, dtype=np.int32), (B, 1))
        cache, lg, ex = served_step(cfg, eng, mesh, at)(
            engine.params, cache, toks[:, t0:t1], pos, tables)
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
                             np.full((B, 1), T + k, np.int32), tables)
        logits.append(np.asarray(lg, np.float32)[:, 0])
        routed.append(np.asarray(ex))
    del cache
    return (np.concatenate(probes, axis=1), np.asarray(where),
            np.stack(logits, axis=1), np.stack(chosen, axis=1),
            np.concatenate(routed, axis=2))


def compare(engine, seed: int, B: int = 2, T: int = T_PROMPT,
            ref_params=None, n_decode: int = N_DECODE, chunk: int = CHUNK,
            variant: str = "") -> dict:
    import numpy as np

    from dynamo_tpu.engine import model as M

    from benchmarks.chip.reference import gaps
    from benchmarks.chip.shape import tokens_for

    cfg = engine.model_config
    if (set(cfg.layer_types) != {LATENT} or not cfg.moe_shortcut
            or not cfg.q_lora_rank):
        raise ValueError("longcat judges a table of double layers of q-LoRA "
                         f"mla_attention rows, not {cfg.layer_types}")
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    toks = np.asarray([tokens_for(seed, "ref", b, T, cfg.vocab_size)
                       for b in range(B)], np.int32)
    probes, where, decoded, chosen, experts = served(
        engine, toks, chunk, n_decode + 1)
    params = engine.params if ref_params is None else ref_params
    ref_probes, ref_decoded = [], []
    route = {"token_layers": 0, "near_ties": 0, "flipped": 0,
             "short_max": 0.0, "zero_strangers": 0,
             "served_zero_pairs": int((experts >= cfg.num_routed_experts
                                       ).sum()),
             "served_pairs": int(experts.size), "tie_gap": TIE_GAP}
    router = {"weight_rel_max": 0.0, "set_mismatch": 0, "tokens": 0}
    for b in range(B):
        full = np.concatenate([toks[b], chosen[b, :n_decode]])
        hidden, routing, router_io = reference_hidden(
            cfg, params, full, variant,
            choices=None if variant == "own_topk" else experts[:, b])
        ref_probes.append(np.asarray(
            head_logits(cfg, params, hidden[where]), np.float32))
        ref_decoded.append(np.asarray(
            head_logits(cfg, params, hidden[T - 1:T + n_decode]), np.float32))
        for gap, flipped, short, strangers in routing:     # every position
            route["token_layers"] += int(gap.size)
            route["near_ties"] += int((np.asarray(gap) < TIE_GAP).sum())
            route["flipped"] += int(np.asarray(flipped).sum())
            route["short_max"] = max(route["short_max"],
                                     float(np.asarray(short).max()))
            route["zero_strangers"] += int(np.asarray(strangers).sum())
        got = program_router(cfg, params, router_io, variant)
        router["weight_rel_max"] = max(router["weight_rel_max"],
                                       got["weight_rel_max"])
        router["set_mismatch"] += got["set_mismatch"]
        router["tokens"] += got["tokens"]
    route["flipped_share"] = route["flipped"] / max(1, route["token_layers"])
    route["zero_strangers_share"] = (route["zero_strangers"]
                                     / max(1, route["served_zero_pairs"]))
    out = {"B": B, "T": T, "n_decode": n_decode, "chunk": chunk,
           "probes": [int(w) for w in where],
           "rel_tol": REL_TOL, "short_tol": SHORT_TOL, "variant": variant,
           "decode_attention": dict(M.ATTENTION_TRACES.get("decode", {})),
           "routing": route, "router": router}
    ok = True
    for name, s, r in (("prefill", probes, np.stack(ref_probes)),
                       ("decode", decoded[:, 1:],
                        np.stack(ref_decoded)[:, 1:])):
        out[name] = g = gaps(s, r)
        ok = (ok and g["finite"]
              and g["max_abs_diff"] <= REL_TOL * g["max_abs_ref"])
    ok = ok and route["short_max"] <= SHORT_TOL
    # a probe alone, both sequences: a fault behind a chunk boundary shows
    # at 512-515 and 1024-1027 and not at 0-3
    ref_p = np.stack(ref_probes)
    out["prefill"]["rms_rel_by_probe"] = [
        float(np.sqrt(np.mean((probes[:, i] - ref_p[:, i]) ** 2)
                      / np.mean(ref_p[:, i] ** 2)))
        for i in range(len(where))]
    p, d = out["prefill"], out["decode"]
    n_p, n_d = probes.size, decoded[:, 1:].size
    out["both"] = {"rms_rel": float(np.sqrt(
        (n_p * p["rms_diff"] ** 2 + n_d * d["rms_diff"] ** 2)
        / (n_p * p["rms_ref"] ** 2 + n_d * d["rms_ref"] ** 2)))}
    out["ok"] = ok
    return out
