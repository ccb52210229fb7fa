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
positions.  It judges every configuration whose file names no ``reference``;
the contract it keeps, and that of the modules a file can name, is the
docstring of ``references/__init__.py``.
"""

from __future__ import annotations

import dataclasses
import functools

# tolerance, with its reason: the served path holds weights and activations
# in bfloat16 (8 bits of mantissa, relative rounding 2^-9 = 0.002 per value)
# and accumulates in float32.  Over the residual stream the roundings add up
# like a random walk: on the chip the largest difference reads 1.1-1.6% of
# the largest logit on 16 layers and 1.6-2.2% on 32 (PERF.md, PRs 27-28).
# This limit refuses int8 weights (4.6-6.2% on 16 layers), fp8 weights
# (14-16%), a wrong mask, a wrong RoPE base or a dropped layer (tens of
# percent; tests/test_references.py here keeps the last two).  It does NOT
# refuse an int8 cache (1.9-2.5% on 16 layers): a maximum over 65536 logits
# swings too much to part 1.5 times.  ``both.rms_rel`` below is steady to 3%
# from seed to seed and does; its limit is read per configuration on the
# chip, kept in ``limits/<configuration>.json`` and applied on top of this
# one by the caller (``worker_launch.judge``).
REL_TOL = 0.03       # max |system - reference| over max |reference|


def rms_norm(v, g, eps):
    import jax
    import jax.numpy as jnp

    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g


def attention_block(x, pos, w, n_heads, n_kv, hd, theta, eps):
    """``x`` plus its pre-normed, rotate-half-roped, grouped-query causal
    attention; ``w`` holds one layer's float32 ``attn_norm wq wk wv wo``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def rope(v):                       # [B, T, H, hd], rotate-half
        half = hd // 2
        inv = 1.0 / (theta ** (jnp.arange(half, dtype=f32) / half))
        ang = pos.astype(f32)[..., None] * inv          # [B, T, half]
        c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
        a, b = v[..., :half], v[..., half:]
        return jnp.concatenate([a * c - b * s, b * c + a * s], -1)

    B, T, _ = x.shape
    h = rms_norm(x, w["attn_norm"], eps)
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
    return x + o @ w["wo"]


def _layer(x, pos, lw, n_heads, n_kv, hd, theta, eps):
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in lw.items()}
    x = attention_block(x, pos, w, n_heads, n_kv, hd, theta, eps)
    h = rms_norm(x, w["mlp_norm"], eps)
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


def served_logits(engine, toks):
    """What the program serves for ``toks`` [B, T]: the last position's
    logits of a prefill through the engine's ``forward`` and a paged cache,
    the greedy next token, and the logits of one decode step on it through
    the decode attention path.  Any reference may drive the program so."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import model as M

    cfg, mesh = engine.model_config, engine.mesh
    eng = dataclasses.replace(engine.config, num_blocks=64)
    multi = mesh is not None and mesh.devices.size > 1
    cache = (M.init_cache_sharded(cfg, eng, mesh) if multi
             else M.init_cache(cfg, eng))
    B, T = toks.shape
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
    return lg_pre, nxt, lg_dec


def gaps(sysl, refl) -> dict:
    """The numbers compared, of served logits against the reference's."""
    import numpy as np

    sysl = np.asarray(sysl, np.float32)
    scale = float(np.max(np.abs(refl)))
    diff = float(np.max(np.abs(sysl - refl)))
    rms_diff = float(np.sqrt(np.mean((sysl - refl) ** 2)))
    rms_ref = float(np.sqrt(np.mean(refl ** 2)))
    return {"max_abs_diff": diff, "max_abs_ref": scale, "rel": diff / scale,
            "rms_diff": rms_diff, "rms_ref": rms_ref,
            "rms_rel": rms_diff / rms_ref,
            "finite": bool(np.isfinite(sysl).all()),
            "greedy_equal": bool(
                (sysl.argmax(-1) == refl.argmax(-1)).all())}


def compare_with(logits_fn, rel_tol, engine, seed: int, B: int, T: int,
                 ref_params=None) -> dict:
    """Prefill ``B`` seeded sequences of ``T`` tokens through the engine's
    forward and a paged cache, decode one more token through the decode
    attention path, and compare both logits with those of ``logits_fn(cfg,
    params, tokens)``.  That forward reads the engine's weights, or
    ``ref_params`` where the engine holds them quantised."""
    import numpy as np

    from dynamo_tpu.engine import model as M

    from .shape import tokens_for

    cfg = engine.model_config
    toks = np.asarray([tokens_for(seed, "ref", b, T, cfg.vocab_size)
                       for b in range(B)], np.int32)
    lg_pre, nxt, lg_dec = served_logits(engine, toks)
    full = np.concatenate([toks, nxt[:, None]], axis=1)
    params = engine.params if ref_params is None else ref_params
    ref = np.asarray(logits_fn(cfg, params, full), np.float32)
    out = {"B": B, "T": T, "rel_tol": rel_tol,
           "decode_attention": dict(M.ATTENTION_TRACES.get("decode", {}))}
    ok = True
    for name, sysl, refl in (("prefill", lg_pre, ref[:, T - 1]),
                             ("decode", lg_dec, ref[:, T])):
        out[name] = g = gaps(sysl, refl)
        ok = (ok and g["finite"]
              and g["max_abs_diff"] <= rel_tol * g["max_abs_ref"])
    # rms over rms of prefill and decode pooled: twice the logits, so the
    # steadiest number here, and the one a configuration's limit addresses
    p, d = out["prefill"], out["decode"]
    out["both"] = {"rms_rel": float(np.sqrt(
        (p["rms_diff"] ** 2 + d["rms_diff"] ** 2)
        / (p["rms_ref"] ** 2 + d["rms_ref"] ** 2)))}
    out["ok"] = ok
    return out


def compare(engine, seed: int, B: int = 2, T: int = 64,
            ref_params=None) -> dict:
    return compare_with(reference_logits, REL_TOL, engine, seed, B, T,
                        ref_params)
