"""The configuration's plain reference: a Mistral/Llama-class decoder
forward in straightforward ``jax.numpy`` and float32 — no kernel, no cache, no
batching tricks, ``highest`` matmul precision, one layer's weights upcast at a
time.  It follows the published description (``MistralForCausalLM``: RMSNorm,
rotate-half RoPE at ``rope_theta``, grouped-query causal attention without a
sliding window in v0.3, SwiGLU MLP, untied head).  It reads the served
engine's weight pytree (stacked per-layer leaves ``wq wk wv wo w_gate w_up
w_down attn_norm mlp_norm``) and shares no code with ``engine/model.py``.

``compare`` is the comparison that decides the configuration's half of
``correct``: prefill through the engine's paged cache, then one decode step
through the decode kernel, against this forward's logits at the same
positions.
"""

from __future__ import annotations

import dataclasses
import functools

# tolerance, with its reason: the served path holds weights and activations
# in bfloat16 (8 bits of mantissa, relative rounding 2^-9 = 0.002 per value)
# and accumulates in float32.  Over the residual stream of 16 to 32 layers
# the roundings add up like a random walk to about 0.5% to 1% of the logits'
# scale (first chip reading is recorded in PERF.md).  A path computing in
# fp8/int8 weights or an int8 cache rounds at 2^-4 to 2^-7 per value — an
# order of magnitude above — and fails this bound; so does a wrong mask, a
# wrong RoPE base (theta 5e5 for 1e6) or a dropped layer, which move logits
# by tens of percent.
REL_TOL = 0.03       # max |system - reference| over max |reference|


def _layer(x, pos, lw, n_heads, n_kv, hd, theta, eps):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = {k: v.astype(f32) for k, v in lw.items()}

    def rms(v, g):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g

    def rope(v):                       # [B, T, H, hd], rotate-half
        half = hd // 2
        inv = 1.0 / (theta ** (jnp.arange(half, dtype=f32) / half))
        ang = pos.astype(f32)[..., None] * inv          # [B, T, half]
        c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
        a, b = v[..., :half], v[..., half:]
        return jnp.concatenate([a * c - b * s, b * c + a * s], -1)

    B, T, _ = x.shape
    h = rms(x, w["attn_norm"])
    q = rope((h @ w["wq"]).reshape(B, T, n_heads, hd))
    k = rope((h @ w["wk"]).reshape(B, T, n_kv, hd))
    v = (h @ w["wv"]).reshape(B, T, n_kv, hd)
    g = n_heads // n_kv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(f32(hd))
    mask = pos[:, None, :, None] >= pos[:, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, n_heads * hd)
    x = x + o @ w["wo"]
    h = rms(x, w["mlp_norm"])
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def reference_logits(cfg, params, tokens):
    """float32 logits [B, T, V] of the whole sequences ``tokens`` [B, T]."""
    import jax
    import jax.numpy as jnp

    hd = cfg.head_dim or cfg.hidden_size // cfg.num_heads
    layer = jax.jit(functools.partial(
        _layer, n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, hd=hd,
        theta=cfg.rope_theta, eps=cfg.rms_norm_eps))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        names = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                 "w_up", "w_down")
        for li in range(cfg.num_layers):
            x = layer(x, pos, {k: params["layers"][k][li] for k in names})
        g = params["final_norm"].astype(jnp.float32)
        x = x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps) * g
        head = (params["embed"].T if cfg.tie_word_embeddings
                else params["lm_head"]).astype(jnp.float32)
        return x @ head


def compare(engine, seed: int, B: int = 2, T: int = 64) -> dict:
    """Prefill ``B`` seeded sequences of ``T`` tokens through the engine's
    forward and a paged cache, decode one more token through the decode
    attention path, and compare both logits with the reference's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import model as M

    from .shape import tokens_for

    cfg, mesh = engine.model_config, engine.mesh
    eng = dataclasses.replace(engine.config, num_blocks=64)
    multi = mesh is not None and mesh.devices.size > 1
    cache = (M.init_cache_sharded(cfg, eng, mesh) if multi
             else M.init_cache(cfg, eng))
    toks = np.asarray([tokens_for(seed, "ref", b, T, cfg.vocab_size)
                       for b in range(B)], np.int32)
    pos = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    W = eng.max_blocks_per_seq
    nb = T // eng.block_size + 1
    tables = np.zeros((B, W), np.int32)
    for b in range(B):
        tables[b, :nb] = 1 + b * nb + np.arange(nb)

    def run(params, cache, tok, p, tb):
        cache, h = M.forward(cfg, eng, params, cache, tok, p, tb, mesh=mesh)
        return cache, M.logits_fn(cfg, params, h[:, -1])

    fn = jax.jit(run, donate_argnums=(1,))
    cache, lg_pre = fn(engine.params, cache, toks, pos, tables)
    nxt = np.asarray(jnp.argmax(lg_pre, -1)).astype(np.int32)
    cache, lg_dec = fn(engine.params, cache, nxt[:, None],
                       np.full((B, 1), T, np.int32), tables)
    del cache
    full = np.concatenate([toks, nxt[:, None]], axis=1)
    ref = np.asarray(reference_logits(cfg, engine.params, full), np.float32)
    out = {"B": B, "T": T, "rel_tol": REL_TOL,
           "decode_attention": dict(M.ATTENTION_TRACES.get("decode", {}))}
    ok = True
    for name, sysl, refl in (("prefill", lg_pre, ref[:, T - 1]),
                             ("decode", lg_dec, ref[:, T])):
        sysl = np.asarray(sysl, np.float32)
        scale = float(np.max(np.abs(refl)))
        diff = float(np.max(np.abs(sysl - refl)))
        out[name] = {"max_abs_diff": diff, "max_abs_ref": scale,
                     "rel": diff / scale,
                     "rms_diff": float(np.sqrt(np.mean((sysl - refl) ** 2))),
                     "greedy_equal": bool(
                         (sysl.argmax(-1) == refl.argmax(-1)).all())}
        ok = ok and bool(np.isfinite(sysl).all()) and diff <= REL_TOL * scale
    out["ok"] = ok
    return out
