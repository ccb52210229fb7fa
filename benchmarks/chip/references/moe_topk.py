"""Plain reference of the program's mixture-of-experts decoder: the
Llama-class attention block of ``reference.py``, then in every layer a router
``softmax(h W_r)`` whose top ``k`` experts (weights renormalised over the
chosen) each run a SwiGLU FFN on the token.  **Every token is kept**: there is
no capacity and no drop.  The program's ``parallel/moe.py`` is a GShard
capacity dispatch that drops a token's expert where the expert's buffer is
full, so a configuration that names this reference sets
``moe_capacity_factor`` to experts / k (a buffer then holds every token of a
call) and says so; below that the two differ by design and ``ok`` is false.

It reads the engine's stacked leaves ``attn_norm wq wk wv wo mlp_norm
w_router`` and ``w_gate w_up w_down`` of shape ``[L, E, ...]``, in float32 at
``highest``, every expert on every token and the unchosen weighted 0: no
dispatch, no cache, no kernel, nothing of ``engine/model.py`` or
``parallel/moe.py``.

Sequences: ``B`` x ``T`` seeded tokens, so each of 8 experts sees tens of
tokens at the defaults; nothing in this class acts only past some length.

Tolerance: ``REL_TOL`` of the largest reference logit, the dense reference's
3% for a bfloat16 served path, for the same reason (roundings of 2^-9 a value
add up over the layers like a random walk).  It refuses what moves a token's
FFN wholesale: an expert's weights lost, top-k off by one, weights not
renormalised, a dropped token.  Routing is a discrete choice: where two
experts' scores tie to within rounding the served path may choose the other
one, and one such token can exceed the limit; a configuration reads its own
limit on the chip (``limits/<configuration>.json``) before it names this
module.  Only the float32 toy has been read: differences of 1e-6.
"""

from __future__ import annotations

import functools

REL_TOL = 0.03       # max |system - reference| over max |reference|


def _layer(x, pos, lw, n_heads, n_kv, hd, theta, eps, top_k):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.reference import attention_block, rms_norm

    w = {k: v.astype(jnp.float32) for k, v in lw.items()}
    x = attention_block(x, pos, w, n_heads, n_kv, hd, theta, eps)
    h = rms_norm(x, w["mlp_norm"], eps)
    probs = jax.nn.softmax(h @ w["w_router"], axis=-1)          # [B, T, E]
    kth = jnp.sort(probs, axis=-1)[..., -top_k][..., None]
    chosen = jnp.where(probs >= kth, probs, 0.0)
    weight = chosen / jnp.sum(chosen, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(w["w_router"].shape[-1]):
        y = (jax.nn.silu(h @ w["w_gate"][e]) * (h @ w["w_up"][e])
             ) @ w["w_down"][e]
        out = out + weight[..., e:e + 1] * y
    return x + out


def reference_logits(cfg, params, tokens):
    """float32 logits [B, T, V] of the whole sequences ``tokens`` [B, T]."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.reference import rms_norm

    layer = jax.jit(functools.partial(
        _layer, n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
        hd=cfg.head_dim or cfg.hidden_size // cfg.num_heads,
        theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
        top_k=cfg.num_experts_per_token))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        names = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_router",
                 "w_gate", "w_up", "w_down")
        for li in range(cfg.num_layers):
            x = layer(x, pos, {k: params["layers"][k][li] for k in names})
        x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                     cfg.rms_norm_eps)
        head = (params["embed"].T if cfg.tie_word_embeddings
                else params["lm_head"]).astype(jnp.float32)
        return x @ head


def compare(engine, seed: int, B: int = 2, T: int = 64,
            ref_params=None) -> dict:
    """As ``reference.compare``: a prefill through the engine's forward and a
    paged cache, one decode step, both logits against this forward's."""
    from benchmarks.chip.reference import compare_with

    if not engine.model_config.is_moe:
        raise ValueError("moe_topk judges a mixture-of-experts "
                         "configuration; this one has no experts")
    return compare_with(reference_logits, REL_TOL, engine, seed, B, T,
                        ref_params)
