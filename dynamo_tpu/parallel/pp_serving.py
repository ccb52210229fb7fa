"""Pipeline-parallel SERVING step: the engine's unified prefill/decode
step executed GPipe-style over a ``pp`` mesh axis, with the paged KV cache
stage-sharded by layer.

Layout (all decided by shardings, not code):

- stacked layer params ``[L, ...]`` sharded ``P("pp")`` — stage s owns
  layers ``[s*L/S, (s+1)*L/S)``;
- the paged cache is STACKED ``[L, NB, KV, bs, hd]`` (unlike the
  single-host engine's per-layer list) and sharded ``P("pp")`` on L, so
  each stage scatter-updates only its own layers' blocks in place;
- embed / final_norm / lm_head replicate: embedding and sampling are tiny
  next to the layer stack, and replicating them avoids edge hops.

Schedule: classic GPipe over the BATCH axis — B rows split into M
microbatches, activations hop stage→stage via ``lax.ppermute`` (neighbor
ICI/DCN traffic, one ``[mb, T, D]`` tensor per boundary per tick), bubble
fraction (S-1)/(S+M-1). Decode batches (B up to max_num_seqs) microbatch
well; a single-sequence prefill chunk runs M=1 (full bubble) — prefill
overlap comes from the engine interleaving chunked prefills with decode
batches, the same interleaving it already does.

Correctness notes: bubble ticks write no page (their entries point at
block 0, which gets its own bytes back) so
they can never touch live cache; the causal order within a sequence holds
because each stage processes microbatches in order (the skew only offsets
WHICH tick a microbatch is processed at, never reorders them).

Same signature as ``model.raw_step_fn`` so the engine swaps it in
untouched. SURVEY §2.3 PP; the reference passes --pipeline-parallel-size
through to its engines — here the schedule is ours.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..engine.config import EngineConfig, ModelConfig
from ..engine import model as model_lib
from . import layout
from .layout import AXIS_PP, make_axes_mesh

Cache = dict


def make_pp_mesh(num_stages: int, devices=None) -> Mesh:
    return make_axes_mesh((num_stages,), (AXIS_PP,), devices=devices)


def init_pp_cache(cfg: ModelConfig, eng: EngineConfig) -> Cache:
    """Stacked paged cache [L, NB, KV, bs, hd] (stage-shardable on L)."""
    dt = jnp.dtype(cfg.dtype)
    shape = (cfg.num_layers, eng.num_blocks, cfg.num_kv_heads,
             eng.block_size, cfg.head_dim_)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def pp_cache_shardings(mesh: Mesh, cfg: ModelConfig) -> Cache:
    stage = layout.named(mesh, AXIS_PP)
    return {"k": stage, "v": stage}


def pp_param_shardings(mesh: Mesh, cfg: ModelConfig):
    """Layer stack over pp; everything else replicated."""
    stage = layout.named(mesh, AXIS_PP)
    repl = layout.replicated(mesh)

    layer_names = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"]
    layer_names += (["w_router", "w_gate", "w_up", "w_down"]
                    if cfg.is_moe else ["w_gate", "w_up", "w_down"])
    shardings = {
        "embed": repl,
        "layers": {name: stage for name in layer_names},
        "final_norm": repl,
    }
    if not cfg.tie_word_embeddings:
        shardings["lm_head"] = repl
    return shardings


def _stage_layers(cfg: ModelConfig, eng: EngineConfig, Lp: int,
                  stage_params, lk, lv, h, positions, block_tables,
                  kv_pages):
    """Apply this stage's Lp layers over one microbatch chunk.

    h [mb, T, D]; lk/lv [Lp, NB, KV, bs, hd] (functionally updated).
    The attention path is the gathered-context einsum — inside shard_map
    every stage attends over its own layers' full context."""
    B = h.shape[0]
    bs = eng.block_size
    hd = cfg.head_dim_
    KV = cfg.num_kv_heads
    W = block_tables.shape[1]
    # one kind of layer (raw_pp_step_fn refuses a table): every stage row
    # is the model's one attention kind and its one FFN
    entry, kind = cfg.layer_table[0], cfg.attn_kinds[0]

    for li in range(Lp):
        p = {name: w[li] for name, w in stage_params.items()}
        x, q, k, v = model_lib.attn_inputs(cfg, kind, p, h, positions)
        layer_k = model_lib._kv_write(lk[li], *kv_pages, k)
        layer_v = model_lib._kv_write(lv[li], *kv_pages, v)
        k_all = jnp.take(
            layer_k, block_tables.reshape(-1), axis=0
        ).reshape(B, W, KV, bs, hd).transpose(0, 1, 3, 2, 4).reshape(
            B, W * bs, KV, hd)
        v_all = jnp.take(
            layer_v, block_tables.reshape(-1), axis=0
        ).reshape(B, W, KV, bs, hd).transpose(0, 1, 3, 2, 4).reshape(
            B, W * bs, KV, hd)
        attn = model_lib._attention(q, k_all, v_all, positions)
        h = model_lib.attn_output(cfg, p, h, x, attn)
        h = model_lib.ffn(cfg, entry, p, h)
        lk = lk.at[li].set(layer_k)
        lv = lv.at[li].set(layer_v)
    return h, lk, lv


def raw_pp_step_fn(cfg: ModelConfig, eng: EngineConfig, mesh: Mesh,
                   num_microbatches: int = 4):
    """The pipelined unified step (same signature as raw_step_fn)."""
    model_lib.refuse_table(cfg, what="the pipeline-parallel step (pp_stages)")
    S = mesh.shape[AXIS_PP]
    if cfg.num_layers % S != 0:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pp={S}"
        )
    Lp = cfg.num_layers // S

    def step(params, cache, tokens, positions, block_tables,
             last_idx, rng, temperature, top_k, top_p, seeds):
        B, T = tokens.shape
        M = num_microbatches
        while B % M != 0:   # bucketed B is pow2; clamp M to divide it
            M //= 2
        mb = B // M
        bs = eng.block_size
        W = block_tables.shape[1]

        h0 = jnp.take(params["embed"], tokens, axis=0)   # [B, T, D]
        D = h0.shape[-1]
        h_mb = h0.reshape(M, mb, T, D)
        pos_mb = positions.reshape(M, mb, T)
        tbl_mb = block_tables.reshape(M, mb, W)

        def body(stage_params, ck, cv, h_all, pos_all, tbl_all):
            stage = jax.lax.axis_index(AXIS_PP)
            fwd = [(j, (j + 1) % S) for j in range(S)]
            lk, lv = ck, cv                          # [Lp, NB, KV, bs, hd]
            act = jnp.zeros_like(h_all[0])
            out = jnp.zeros_like(h_all)
            for t in range(M + S - 1):
                feed = h_all[t] if t < M else jnp.zeros_like(h_all[0])
                act = jnp.where(stage == 0, feed, act)
                mb_idx = jnp.clip(t - stage, 0, M - 1)
                valid = ((t - stage) >= 0) & ((t - stage) < M)
                pos = jnp.take(pos_all, mb_idx, axis=0)     # [mb, T]
                tbl = jnp.take(tbl_all, mb_idx, axis=0)     # [mb, W]
                # bubble ticks must not touch live cache: only valid
                # in-window microbatches with real positions write a page
                kv_pages = model_lib._kv_pages(
                    jnp.where(valid, pos, -1), tbl, bs)
                y, lk, lv = _stage_layers(
                    cfg, eng, Lp, stage_params, lk, lv, act, pos, tbl,
                    kv_pages,
                )
                act = jnp.where(valid, y, act)
                bank = (stage == S - 1) & valid
                sel = (jnp.arange(M) == jnp.clip(t - stage, 0, M - 1))[
                    (slice(None),) + (None,) * (out.ndim - 1)
                ]
                out = jnp.where(bank & sel, act[None], out)
                if t != M + S - 2:
                    act = jax.lax.ppermute(act, AXIS_PP, fwd)
            out = jnp.where(stage == S - 1, out, jnp.zeros_like(out))
            return jax.lax.psum(out, AXIS_PP), lk, lv

        stage_spec = layout.spec(AXIS_PP)
        repl_spec = layout.spec()
        h_out, new_k, new_v = layout.shard_map(
            body, mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: stage_spec, params["layers"]),
                stage_spec, stage_spec, repl_spec, repl_spec, repl_spec,
            ),
            out_specs=(repl_spec, stage_spec, stage_spec),
        )(params["layers"], cache["k"], cache["v"], h_mb, pos_mb, tbl_mb)

        h = h_out.reshape(B, T, D)
        h = model_lib._rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        h_last = h[jnp.arange(B), last_idx]
        logits = model_lib.logits_fn(cfg, params, h_last)
        pos_last = jnp.take_along_axis(
            positions, last_idx[:, None], axis=1
        )[:, 0]
        sampled = model_lib.sample(
            logits, rng, temperature, top_k, top_p, seeds, pos_last
        )
        return {"k": new_k, "v": new_v}, sampled

    return step


def make_pp_step_fn(cfg: ModelConfig, eng: EngineConfig, mesh: Mesh,
                    num_microbatches: int = 4):
    return jax.jit(
        raw_pp_step_fn(cfg, eng, mesh, num_microbatches),
        donate_argnums=(1,),
    )
