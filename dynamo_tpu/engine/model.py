"""Llama-class transformer in functional JAX with a paged KV cache.

This is the compute core the reference delegates to vLLM (ref: components/
backends/vllm/src/dynamo/vllm/main.py:97 ``setup_vllm_engine``); here it is
TPU-native. Design points:

- **One unified step function** serves both prefill chunks and decode batches:
  ``tokens [B, T]`` with per-sequence block tables. Prefill runs ``B=1`` with a
  bucketed ``T``; decode runs ``T=1`` with a bucketed ``B``. XLA compiles one
  program per (B, T, W) bucket combination.
- **Layers are unrolled** over stacked parameters. A static per-layer slice
  is a read inside the matmul's fusion as long as the product stays the 2-D
  matmul it is written as; :func:`_qkv_proj` says what happens when it does
  not. The paged KV cache is per-layer arrays so each buffer is donated and
  scatter-updated IN PLACE — threading a stacked cache through ``lax.scan``
  costs whole-cache copies every step.
- **Paged KV**: the cache is ``[L, num_blocks, KV, block_size, hd]``
  (block-major, head-contiguous); the step writes the chunk's K/V into the
  pages its block table names, then attends — decode via the
  Pallas paged kernel streaming blocks HBM→VMEM (ops/paged_attention.py),
  prefill via a gathered-context einsum. Physical block 0 is the null block:
  the allocator never hands it out, pads and dead rows point at it, and no
  step program modifies it — under serving alone it holds what
  :func:`init_cache` put there (zeros, and zero scales) for the life of the
  engine. (The block movers of :func:`make_kv_ops` still send the pads of a
  padded id list there, so nothing may READ it for meaning: the kernel's
  masks hold whatever its bytes are.)
- **The write keeps the kernel's layout** (:func:`_kv_write`). The kernel
  reads a layer row-major, ``{3,2,1,0}``. The plain
  ``lk.at[block, :, off].set(k)`` indexes dims 0 and 2 around the window dim
  ``KV``; XLA's layout assignment gives such a scatter the operand layout
  ``{3,1,2,0}`` (block, token, head, hd) and converts with whole-layer
  ``copy`` ops: K in, K out, V in, V out per layer, 64 copies of 32 MiB a
  16-layer step, half of every step program on a v5e (PERF.md, PR 29). A
  scatter whose indexed dim leads takes the layer row-major and converts
  nothing; PR 29's wrote one ``hd`` row per (token, head) through the
  ``[NB*KV*bs, hd]`` view, and XLA's TPU scatter walks its updates one by
  one at ~70 ns each whatever their size: 4.4 ms of a 16.3 ms T=256 chunk.
  So the step writes WHOLE PAGES, as many updates as pages touched
  (PERF.md, PR 33): a row of the ``[B, T]`` chunk holds contiguous
  positions, so it touches at most ``(T + bs - 2) // bs + 1`` pages; read
  them (a gather on dim 0), lay the new rows over them with a ``where``,
  and scatter the merged pages back on dim 0 alone. Real blocks come out
  with the same bits; an entry no valid token falls on is redirected to
  block 0 and writes block 0's own bytes back, so duplicates cannot race
  and block 0 never changes. ``tests/test_chip_compile.py`` holds the
  compiled step programs to "no copy of a cache layer" and "no scatter
  with more updates than pages".
- **TP via shardings, not code**: parameters and cache carry
  ``jax.sharding.NamedSharding`` annotations over a ``("dp", "tp")`` mesh
  (attention/MLP column-row sharded, KV heads sharded over tp); XLA GSPMD
  inserts the all-reduces the reference gets from NCCL inside vLLM.
- **Sampling is fused** into the step (greedy / temperature / top-k / top-p,
  per-request seeds) so only
  B sampled token ids cross the host boundary per step, not ``[B, vocab]``
  logits.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from ..observability import compilewatch
from ..parallel import layout
from ..parallel.layout import AXIS_TP, SpecLayout, make_mesh
from . import quant
from .config import (KV_KINDS, LATENT_KIND, STATE_KIND, EngineConfig,
                     ModelConfig)

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]

# ``jax.named_scope`` names the step programs carry in every op's
# ``op_name`` metadata (no layer index: one name per stage, summed over
# layers and window steps). Metadata only — a scope changes no HLO op. The
# tests and the benchmark's trace reader (benchmarks/chip/scopes.py) import
# this tuple, so a stage is renamed here or nowhere.
SCOPES = (
    "embed",       # token gather (+ multimodal splice)
    "qkv_proj",    # attn norm + q/k/v matmuls
    "rope",
    "kv_write",    # scatter of the chunk's K/V (and scales) into the cache
    "attention",   # the Pallas call, the ring, or gather + einsum
    "o_proj",      # output matmul + residual
    "mlp",         # mlp norm, gate/up/down (or moe_ffn) + residual
    "final_norm",
    "lm_head",
    "sample",
    "ctl",         # token ring / autopilot control-state reads and deltas
)
# the stages only a table's layers have (ModelConfig.layer_types; PR 32),
# added behind the others: none of those was renamed
TABLE_SCOPES = (
    "attention_window",  # "attention" of a layer with a window: its walk
                         # starts at the window, so the trace parts the two
    "attn_gate",   # per-head sigmoid gate on the attention output
    "moe_router",  # mlp norm, router matmul, softmax, top-k, sort
    "moe_experts",  # dispatch, grouped matmuls over the experts held, combine
    "moe_shared",  # the shared expert + residual
    # a linear-attention (KDA) layer, whose memory is its seat's state (PR 34)
    "kda_proj",    # attn norm + q / k / v / decay / beta matmuls
    "kda_conv",    # the short convolution, its state, SiLU, the L2 norms
    "kda_recurrent",  # T = 1: the token recurrence over the seats' states
    "kda_chunk",   # T > 1: the chunked form over a prefill chunk
    "kda_out",     # the per-head norm of the output
    "attention_latent",  # "attention" of a layer that keeps a latent (MLA)
    # a sparse layer whose router also chooses zero-compute experts, and
    # whose sum joins the stream a row later (``moe_shortcut``; PR 41)
    "moe_zero",    # the identity experts' part: (sum of their weights) * x
    "moe_shortcut",  # the pending routed sum joins behind the next row's FFN
    # a gated-delta-rule layer (one decay a head, dk x dv states; PR 45).
    # A sublayer's output norm (``norm_placement`` "post") is in "o_proj" /
    # "mlp", the QK-norm over the projections in "qkv_proj"
    "gdn_proj",    # q / k / v / decay / beta matmuls on the raw stream
    "gdn_conv",    # the short convolution, its state, SiLU, the L2 norms
    "gdn_recurrent",  # T = 1: the token recurrence over the seats' states
    "gdn_chunk",   # T > 1: the scalar-gate chunked form over a chunk
    "gdn_out",     # the per-head norm of the output times its SiLU gate
)
SCOPES += TABLE_SCOPES

_LANES = 128


# ------------------------------ init ------------------------------------


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _normal(key, shape, fan_in, dt):
    return (jax.random.normal(key, shape, jnp.float32)
            / np.sqrt(fan_in)).astype(dt)


# one routed-expert leaf of one layer, a program of its own: its float32
# draw (1.6 GB at 128 x 3072 x 1024) is dead before the next one starts
_expert_leaf = jax.jit(_normal, static_argnums=(1, 2, 3))

# the expert leaves of a table: a list of per-layer ``[Eh, in, out]`` arrays
# (not one stack: a grouped matmul is a custom call, and a static slice of a
# stack as its operand is a 1.6 GB copy a leaf a layer a step)
EXPERT_LEAVES = ("expert_gate", "expert_up", "expert_down")


def latent_width(cfg: ModelConfig) -> int:
    """Values a token keeps in a latent page: ``kv_lora_rank`` +
    ``qk_rope_head_dim``, in whole lane tiles (the chip stores a plane's
    minor dim in tiles of 128 whatever it is declared as, and the kernel's
    DMA takes whole tiles); what is past the two is zeros."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // _LANES) * _LANES


def _init_table_small(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Everything of a table's parameters but the routed experts: leaves
    that every layer has alike are one ``[L, ...]`` stack, ``wk`` / ``wv``
    one stack over the layers that keep K and V, ``wq`` / ``wo`` / the gate
    one stack an attention kind, a kind's own leaves (``kda_*``, ``mla_*``)
    one stack each, the dense FFN and the sparse layers' router and shared
    expert one stack an FFN kind."""
    dt = _dtype(cfg)
    hd, D, KV, L, V = (cfg.head_dim_, cfg.hidden_size, cfg.num_kv_heads,
                       cfg.num_layers, cfg.vocab_size)
    key = iter(jax.random.split(rng, 64))
    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    n_kv = sum(t in KV_KINDS for t in cfg.layer_types)
    if n_kv:
        layers["wk"] = _normal(next(key), (n_kv, D, KV * hd), D, dt)
        layers["wv"] = _normal(next(key), (n_kv, D, KV * hd), D, dt)
    if n_kv and cfg.qk_norm:
        layers["k_norm"] = jnp.ones((n_kv, KV * hd), dt)
        layers["q_norm"] = {
            kind.name: jnp.ones((len(kind.layers), kind.num_heads * hd), dt)
            for kind in cfg.attn_kinds if kind.name in KV_KINDS}
    layers["wq"], layers["wo"] = {}, {}
    if cfg.attn_gate:
        layers["w_attn_gate"] = {}
    for kind in cfg.attn_kinds:
        n, H = len(kind.layers), kind.num_heads
        q_dim, o_dim = hd, hd
        if kind.name == LATENT_KIND:
            q_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            o_dim = cfg.v_head_dim
        if kind.name == STATE_KIND and cfg.gated_delta:
            q_dim, o_dim = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        # behind a q-LoRA ``wq`` is Wqb, on Wqa's normed output.  The
        # published scale sqrt(hidden / rank) is there to give a projection
        # drawn for ``hidden`` inputs unit variance behind ``rank`` of them:
        # draw it so.  Drawn for ``rank`` the scaled scores spread 6 wide,
        # attention is an argmax, and a bfloat16 rounding grows eightfold a
        # layer (PERF.md, PR 41)
        q_in = (cfg.q_lora_rank if kind.name == LATENT_KIND
                and cfg.q_lora_rank else D)
        q_fan = D if cfg.mla_scale_q_lora else q_in
        layers["wq"][kind.name] = _normal(
            next(key), (n, q_in, H * q_dim), q_fan, dt)
        layers["wo"][kind.name] = _normal(
            next(key), (n, H * o_dim, D), H * o_dim, dt)
        if cfg.attn_gate:
            layers["w_attn_gate"][kind.name] = _normal(
                next(key), (n, D, H), D, dt)
        if kind.name == STATE_KIND and cfg.gated_delta:
            K, dk, dv = (cfg.linear_conv_kernel_dim, q_dim, o_dim)
            layers["gdn_wk"] = _normal(next(key), (n, D, H * dk), D, dt)
            layers["gdn_wv"] = _normal(next(key), (n, D, H * dv), D, dt)
            layers["gdn_wa"] = _normal(next(key), (n, D, H), D, dt)
            layers["gdn_wb"] = _normal(next(key), (n, D, H), D, dt)
            layers["gdn_conv"] = _normal(
                next(key), (n, K, H * (2 * dk + dv)), K, dt)
            # one rate and one bias a head (float32 leaves), drawn as KDA's
            # below and for its reason: g = -exp(A_log) softplus(x Wa +
            # dt_bias) with x Wa ~ N(0, 1) gives a head a mean decay of
            # ~0.98 (bias -4.5) to ~0.9998 (bias -9) a token, lower where
            # the raw stream a post-norm model's layers read has grown
            layers["gdn_a_log"] = 0.2 * jax.random.normal(
                next(key), (n, H), jnp.float32)
            layers["gdn_dt_bias"] = jax.random.uniform(
                next(key), (n, H), jnp.float32, -9.0, -4.5)
            layers["gdn_o_norm"] = jnp.ones((n, dv), dt)
            if cfg.linear_gate:
                layers["gdn_wg"] = _normal(next(key), (n, D, H * dv), D, dt)
        elif kind.name == STATE_KIND:
            K = cfg.short_conv_kernel_size
            for name in ("kda_wk", "kda_wv", "kda_wf"):
                layers[name] = _normal(next(key), (n, D, H * hd), D, dt)
            layers["kda_w_beta"] = _normal(next(key), (n, D, H), D, dt)
            layers["kda_conv"] = _normal(next(key), (n, K, 3 * H * hd), K, dt)
            # the decay's rate a head and bias a channel (assumed; float32
            # leaves), drawn so that a token's decay lies near 1 as a
            # trained layer's does and the publication's initialisation
            # gives: with x Wf ~ N(0, 1) a channel's mean decay runs from
            # ~0.9 (bias -4.5) to ~0.999 (bias -9), a memory of ten to a
            # thousand tokens.  A state that forgets in two or three tokens
            # would hide what the seat carries across steps and chunks
            layers["kda_a_log"] = 0.2 * jax.random.normal(
                next(key), (n, H), jnp.float32)
            layers["kda_dt_bias"] = jax.random.uniform(
                next(key), (n, H * hd), jnp.float32, -9.0, -4.5)
            layers["kda_o_norm"] = jnp.ones((n, hd), dt)
        if kind.name == LATENT_KIND:
            r = cfg.kv_lora_rank
            layers["mla_wdkv"] = _normal(
                next(key), (n, D, r + cfg.qk_rope_head_dim), D, dt)
            layers["mla_kv_norm"] = jnp.ones((n, r), dt)
            layers["mla_wukv"] = _normal(
                next(key),
                (n, r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                D if cfg.mla_scale_kv_lora else r, dt)   # as Wqb above
            if cfg.q_lora_rank:
                layers["mla_wqa"] = _normal(
                    next(key), (n, D, cfg.q_lora_rank), D, dt)
                layers["mla_q_norm"] = jnp.ones((n, cfg.q_lora_rank), dt)
    n_dense = cfg.mlp_layer_types.count("dense")
    n_sparse = L - n_dense
    if n_dense:
        F = cfg.intermediate_size
        layers["w_gate"] = _normal(next(key), (n_dense, D, F), D, dt)
        layers["w_up"] = _normal(next(key), (n_dense, D, F), D, dt)
        layers["w_down"] = _normal(next(key), (n_dense, F, D), F, dt)
    if n_sparse:
        Fs = cfg.shared_expert_intermediate_size
        layers["w_router"] = _normal(
            next(key), (n_sparse, D, cfg.router_width), D, dt)
        layers["shared_gate"] = _normal(next(key), (n_sparse, D, Fs), D, dt)
        layers["shared_up"] = _normal(next(key), (n_sparse, D, Fs), D, dt)
        layers["shared_down"] = _normal(next(key), (n_sparse, Fs, D), Fs, dt)
    params: Params = {
        "embed": _normal(next(key), (V, D), D, dt),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _normal(next(key), (D, V), D, dt)
    if n_sparse and cfg.moe_router_enable_expert_bias:
        # drawn behind every other leaf: small beside the scores' spread,
        # and not zero, so that the choice's bias is not a no-op (assumed).
        # A sigmoid's scores spread over tenths; a softmax's over E outputs
        # lie near 1 / E, where 0.05 would pin every token to one set
        std = (0.05 if cfg.score_function == "sigmoid"
               else 0.25 / cfg.router_width)
        layers["router_bias"] = std * jax.random.normal(
            next(key), (n_sparse, cfg.router_width), jnp.float32)
    return params


_init_table_small_jit = jax.jit(_init_table_small, static_argnums=(1,))


def _init_table_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """A table's parameters from ``rng``: the small leaves in one program,
    then each routed-expert leaf of each sparse layer in one of its own, in
    turn, so that no two float32 draws of that size are alive at once."""
    small_key, expert_key = jax.random.split(rng)
    params = _init_table_small_jit(small_key, cfg)
    n_sparse = cfg.mlp_layer_types.count("sparse")
    if n_sparse:
        dt = _dtype(cfg)
        D, F, Eh = (cfg.hidden_size, cfg.moe_intermediate_size,
                    cfg.num_experts)
        shapes = dict(zip(EXPERT_LEAVES, (((Eh, D, F), D), ((Eh, D, F), D),
                                          ((Eh, F, D), F))))
        keys = jax.random.split(expert_key, n_sparse * len(shapes))
        for j, (name, (shape, fan_in)) in enumerate(shapes.items()):
            params["layers"][name] = [
                # at load, not in a step: the next draw starts when this
                # one's float32 copy is gone
                jax.block_until_ready(  # dynalint: disable=DT102
                    _expert_leaf(keys[j * n_sparse + i], shape, fan_in, dt))
                for i in range(n_sparse)]
    return params


def _init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random-init parameters; per-layer leaves are stacked ``[L, ...]``
    and read by a static slice in :func:`forward`'s unrolled loop."""
    dt = _dtype(cfg)
    hd = cfg.head_dim_
    D, H, KV, F, L, V = (
        cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
        cfg.intermediate_size, cfg.num_layers, cfg.vocab_size,
    )
    keys = jax.random.split(rng, 12)

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, D), dt),
        "wq": norm(keys[1], (L, D, H * hd), D),
        "wk": norm(keys[2], (L, D, KV * hd), D),
        "wv": norm(keys[3], (L, D, KV * hd), D),
        "wo": norm(keys[4], (L, H * hd, D), H * hd),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if cfg.is_moe:
        E = cfg.num_experts
        layers["w_router"] = norm(keys[9], (L, D, E), D)
        layers["w_gate"] = norm(keys[5], (L, E, D, F), D)
        layers["w_up"] = norm(keys[6], (L, E, D, F), D)
        layers["w_down"] = norm(keys[7], (L, E, F, D), F)
    else:
        layers["w_gate"] = norm(keys[5], (L, D, F), D)
        layers["w_up"] = norm(keys[6], (L, D, F), D)
        layers["w_down"] = norm(keys[7], (L, F, D), F)
    params: Params = {
        "embed": norm(keys[0], (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(keys[8], (D, V), D)
    return params


# Always one compiled program (cfg is static): XLA folds the scaling into
# the sampler's own constants, so an op-by-op run differs from a compiled
# one in the last bit — every caller, :func:`init_params_sharded` included,
# gets the compiled values and therefore the same ones for the same key.
_init_params_jit = jax.jit(_init_params, static_argnums=(1,))


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    if cfg.has_table:
        return _init_table_params(rng, cfg)
    return _init_params_jit(rng, cfg)


def init_cache(cfg: ModelConfig, eng: EngineConfig) -> Cache:
    """What the layers keep between steps, one list of per-layer arrays a
    kind of memory (``ModelConfig.cache_kinds``); a model has the keys of
    the kinds it has and no others.

    **K and V pages** (``"k"`` / ``"v"``; every layer of a model without a
    table, a table's full and sliding layers): ``[num_blocks, KV,
    block_size, hd]``, block-major and head-contiguous.  One (block, head)
    tile is a contiguous ``bs*hd`` run — the DMA granule the Pallas decode
    kernel streams HBM→VMEM, and the transfer unit for disagg/KVBM block
    movement.  Quantized (``kv_dtype`` int8 / fp8) they are 1 byte an
    element beside per-(slot, head) float32 scale planes ``"ks"`` / ``"vs"``.

    **Latent pages** (``"latent"``; a table's ``mla_attention`` layers):
    ``[num_blocks, 1, block_size, latent_width]``, one vector a token that
    is key and value of every head at once (the normed ``kv_lora_rank``
    values, then the roped ``qk_rope_head_dim``, then zeros up to whole
    lane tiles), in the pages of the same block table: the sequence's
    blocks are counted once whatever kinds its layers are.

    **Seat state** (``"state"`` / ``"conv"``; ``linear_attention`` layers):
    ``[S + 1, H, hd, hd]`` in ``cfg.state_dtype`` and ``[S + 1, K - 1, 3 *
    H * hd]``, indexed by the scheduler's seat (``SchedSeq.slot``, ``S =
    max_num_seqs``): a sequence's state does not grow with its context, so
    it has no pages.  Row ``S`` is the trash seat of pad rows.  A gated-
    delta-rule layer's states are ``dk`` x ``dv``, laid out with heads side
    by side in whole lane tiles (``ops.gated_delta.pool_shape``: ``[S + 1,
    15, 96, 384]`` for 30 heads of 96 x 192), and its convolution runs over
    ``H * (2 dk + dv)`` channels.

    Per-layer arrays (not one stacked [L, …] array) are the TPU-critical
    choice for all three: each layer's buffer is donated and updated IN
    PLACE. A stacked cache threaded through ``lax.scan`` forces XLA to
    slice-out + update-in the whole cache every step — measured ~90 ms/step
    of pure copies on v5e for a 1B model.

    In place also depends on HOW a step writes: pages only through
    :func:`_kv_write`, whose scatter indexes the leading dim alone (whole
    pages) and so accepts this row-major layout (a ``.at[block, :,
    off].set`` on the 4-D array makes XLA copy the whole layer to
    ``{3,1,2,0}`` and back around every write: module header); seat rows
    by a gather and a scatter on the leading dim.

    Block 0 is the null block and is made here: zeros, and zero scales
    for int8 / fp8, which dequantize to exact zeros. No step program
    modifies it — a write that points at it (a pad, a dead row) puts its
    own bytes back (:func:`_kv_write`); only a padded ``inject`` of
    :func:`make_kv_ops` can still land there.  The trash seat likewise: a
    pad or dead row writes back what it read."""
    dt = _dtype(cfg)
    kinds = cfg.cache_kinds
    cache: Cache = {}
    if "kv" in kinds:
        n_kv = (sum(t in KV_KINDS for t in cfg.layer_types)
                if cfg.has_table else cfg.num_layers)
        shape = (eng.num_blocks, cfg.num_kv_heads, eng.block_size,
                 cfg.head_dim_)
        page_dt = dt
        if quant.is_quantized(eng.kv_dtype):
            # quantized pages (1 byte/elem) plus per-(slot, head) f32 scale
            # planes; block 0's zero scales dequantize to exact zeros
            page_dt = quant.storage_dtype(eng.kv_dtype)
        cache["k"] = [jnp.zeros(shape, page_dt) for _ in range(n_kv)]
        cache["v"] = [jnp.zeros(shape, page_dt) for _ in range(n_kv)]
        if quant.is_quantized(eng.kv_dtype):
            cache["ks"] = [jnp.zeros(shape[:-1], jnp.float32)
                           for _ in range(n_kv)]
            cache["vs"] = [jnp.zeros(shape[:-1], jnp.float32)
                           for _ in range(n_kv)]
    for kind in cfg.attn_kinds if cfg.has_table else ():
        n, H, hd = len(kind.layers), kind.num_heads, cfg.head_dim_
        if kind.name == LATENT_KIND:
            cache["latent"] = [
                jnp.zeros((eng.num_blocks, 1, eng.block_size,
                           latent_width(cfg)), dt) for _ in range(n)]
        if kind.name == STATE_KIND and cfg.gated_delta:
            from ..ops.gated_delta import pool_shape

            dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
            S = eng.max_num_seqs
            cache["state"] = [
                jnp.zeros(pool_shape(S + 1, H, dk, dv),
                          jnp.dtype(cfg.state_dtype)) for _ in range(n)]
            cache["conv"] = [
                jnp.zeros((S + 1, cfg.linear_conv_kernel_dim - 1,
                           H * (2 * dk + dv)), dt) for _ in range(n)]
        elif kind.name == STATE_KIND:
            S = eng.max_num_seqs
            cache["state"] = [
                jnp.zeros((S + 1, H, hd, hd), jnp.dtype(cfg.state_dtype))
                for _ in range(n)]
            cache["conv"] = [
                jnp.zeros((S + 1, cfg.short_conv_kernel_size - 1,
                           3 * H * hd), dt) for _ in range(n)]
    return cache


# ---------------------------- shardings ----------------------------------


def param_shardings(mesh: Mesh, cfg: ModelConfig,
                    weight_dtype: str = "bf16") -> Params:
    """The canonical per-parameter table (see ``SpecLayout``): Megatron
    column/row TP over ``tp``, parameter storage over ``fsdp`` when the
    mesh carries one, vocab-sharded embed/lm_head. A quantized
    ``weight_dtype`` mirrors the ``{"q", "s"}`` leaf structure."""
    return SpecLayout.for_mesh(mesh).param_shardings(mesh, cfg,
                                                     weight_dtype)


def cache_shardings(mesh: Mesh, cfg: ModelConfig,
                    kv_dtype: str = "bf16") -> Cache:
    # KV heads sharded over tp so each shard holds the heads it computes
    return SpecLayout.for_mesh(mesh).cache_shardings(mesh, cfg, kv_dtype)


def refuse_table(cfg: ModelConfig, *, what: str = "",
                 mesh: Optional[Mesh] = None,
                 weight_dtype: str = "bf16") -> None:
    """A table of layer kinds (``ModelConfig.layer_types``) runs on one
    device in the model's own dtype through :func:`forward` and
    :func:`encode_forward`.  Whatever else is asked to run one says so when
    it is built, not at its first step: ``SpecLayout`` has no specs for a
    stack a kind, the pipeline stages slice one ``[L, ...]`` stack a leaf,
    and ``quant.quantize_params`` knows the one-kind tree."""
    if not cfg.has_table:
        return
    kinds = sorted(set(cfg.layer_types))
    if what:
        raise ValueError(
            f"{what} has no path for a table of layer kinds "
            f"(layer_types {kinds})")
    if _multi(mesh):
        raise ValueError(
            f"a table of layer kinds (layer_types {kinds}) runs on "
            f"--mesh 1,1; mesh {dict(mesh.shape)} has no sharding specs "
            f"for it")
    if weight_dtype != "bf16":
        raise ValueError(
            f"a table of layer kinds (layer_types {kinds}) is served in "
            f"the model's dtype; --weight-dtype {weight_dtype} has no "
            f"quantiser for it")


def refuse_unpaged(cfg: ModelConfig, what: str) -> None:
    """What moves, skips or rolls back a sequence's memory knows K and V
    pages only.  A table with a latent page or a seat state (``cache_kinds``
    beyond "kv") refuses it when it is built: a block without its
    sequence's state is no prefix, and a state has no earlier version to
    roll back to."""
    if cfg.cache_kinds != ("kv",):
        raise ValueError(
            f"{what} has no path for what layer_types "
            f"{sorted(set(cfg.layer_types))} keep (cache kinds "
            f"{list(cfg.cache_kinds)}): it moves K and V pages only")


def _multi(mesh: Optional[Mesh]) -> bool:
    """Explicit in/out shardings only pay off (and only typecheck against
    axis names) on a real multi-device mesh."""
    return mesh is not None and mesh.devices.size > 1


def _io_kwargs(mesh: Optional[Mesh], cfg: ModelConfig, n_repl_in: int,
               outs: Tuple[str, ...],
               eng: Optional[EngineConfig] = None) -> Dict[str, Any]:
    """``jax.jit`` in/out sharding kwargs for a step-family function whose
    leading args are (params, cache) followed by ``n_repl_in`` replicated
    data/control args. ``outs`` names each output: "cache" (paged-cache
    layout) or "repl". Pinning both sides to the canonical layout means a
    mis-sharded arg is resharded at the boundary instead of silently
    recompiling a differently-partitioned program. ``eng`` (when given)
    carries the quantization dtypes so the scale leaves get their specs."""
    if not _multi(mesh):
        return {}
    wd = eng.weight_dtype if eng is not None else "bf16"
    kd = eng.kv_dtype if eng is not None else "bf16"
    lay = SpecLayout.for_mesh(mesh)
    repl = layout.replicated(mesh)
    pick = {"cache": lay.cache_shardings(mesh, cfg, kd), "repl": repl}
    return {
        "in_shardings": (
            lay.param_shardings(mesh, cfg, wd),
            lay.cache_shardings(mesh, cfg, kd),
        ) + (repl,) * n_repl_in,
        "out_shardings": tuple(pick[o] for o in outs),
    }


def _repl_kwargs(mesh: Optional[Mesh], n_in: int) -> Dict[str, Any]:
    """All-replicated in/out shardings (control-state updates)."""
    if not _multi(mesh):
        return {}
    repl = layout.replicated(mesh)
    return {"in_shardings": (repl,) * n_in, "out_shardings": repl}


def init_params_sharded(rng: jax.Array, cfg: ModelConfig, mesh: Mesh,
                        weight_dtype: str = "bf16") -> Params:
    """Random-init (and quantize) straight into the serving layout.

    One jitted program whose outputs carry ``param_shardings``: each device
    only ever materialises its own shards.  Building the tree on the
    default device first and spreading it afterwards puts the whole model
    on chip 0 — 16 GB of bf16 for the 8B preset, all of a v5e chip's HBM.
    Same values as :func:`init_params` for the same key.  A table is one
    device's (``refuse_table`` holds the meshes off) and is drawn by the
    same programs as :func:`init_params`."""
    if cfg.has_table:
        refuse_table(cfg, mesh=mesh, weight_dtype=weight_dtype)
        return _init_table_params(rng, cfg)
    fn = jax.jit(
        lambda key: quant.quantize_params(_init_params(key, cfg),
                                          weight_dtype),
        out_shardings=param_shardings(mesh, cfg, weight_dtype),
    )
    return fn(rng)


def init_cache_sharded(cfg: ModelConfig, eng: EngineConfig,
                       mesh: Mesh) -> Cache:
    """:func:`init_cache` allocated under ``cache_shardings`` (zeros are
    born on the device that keeps them; see :func:`init_params_sharded`).
    A table with other memory than K and V pages is one device's
    (``refuse_table``) and has no specs to be born under."""
    if cfg.cache_kinds != ("kv",):
        refuse_table(cfg, mesh=mesh)
        return jax.jit(lambda: init_cache(cfg, eng),
                       out_shardings=layout.replicated(mesh))()
    fn = jax.jit(
        lambda: init_cache(cfg, eng),
        out_shardings=cache_shardings(mesh, cfg, eng.kv_dtype),
    )
    return fn()


def shard_params(params: Params, mesh: Mesh, cfg: ModelConfig,
                 weight_dtype: str = "bf16") -> Params:
    if cfg.has_table:
        refuse_table(cfg, mesh=mesh, weight_dtype=weight_dtype)
        return jax.device_put(params, mesh.devices.flat[0])
    return jax.device_put(params, param_shardings(mesh, cfg, weight_dtype))


def shard_cache(cache: Cache, mesh: Mesh, cfg: ModelConfig,
                kv_dtype: str = "bf16") -> Cache:
    return jax.device_put(cache, cache_shardings(mesh, cfg, kv_dtype))


# ----------------------------- modules -----------------------------------


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def rope_frequencies(rope: Dict[str, Any], head_dim: int
                     ) -> Tuple[np.ndarray, float]:
    """Inverse frequencies ``[rotary // 2]`` (float32, computed once at
    trace time) and the factor on ``cos`` and ``sin`` of a rope given by
    the published keys: ``rope_theta``; ``partial_rotary_factor`` (the
    leading share of each head that turns; the rest passes through); and
    for ``rope_type`` "yarn" the blend of ``1 / theta^(2i/d)`` with the same
    over ``factor``, by the linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` turns in
    ``original_max_position_embeddings`` positions, with
    ``attention_factor`` on ``cos`` and ``sin``."""
    theta = float(rope["rope_theta"])
    rotary = int(head_dim * float(rope.get("partial_rotary_factor", 1)))
    half = rotary // 2
    exponent = np.arange(half, dtype=np.float64) / half
    inv = 1.0 / theta ** exponent
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return inv.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(turns: float) -> float:
        return (rotary * np.log(orig / (turns * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(np.ceil(correction_dim(float(rope["beta_slow"]))), rotary - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * np.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def _rotate(x: jax.Array, positions: jax.Array, freqs, scale: float = 1.0,
            interleave: bool = False) -> jax.Array:
    """Rotate-half on the leading ``2 * len(freqs)`` dims of each head of
    ``x [B, T, Hx, hd]``; the rest passes through.  ``interleave``: the
    pairs that turn together are dims ``(2i, 2i + 1)``, not ``(i, i +
    half)``."""
    hd = x.shape[-1]
    half = freqs.shape[0]
    pos = jnp.maximum(positions, 0).astype(jnp.float32)  # [B, T]
    angles = pos[..., None] * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if interleave:
        pairs = x[..., :2 * half].astype(jnp.float32).reshape(
            x.shape[:-1] + (half, 2))
        xf1, xf2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
                        axis=-1).reshape(x.shape[:-1] + (2 * half,)
                                         ).astype(x.dtype)
        if 2 * half < hd:
            return jnp.concatenate([out, x[..., 2 * half:]], axis=-1)
        return out
    x1, x2 = x[..., :half], x[..., half:2 * half]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)
    if 2 * half < hd:
        return jnp.concatenate([out, x[..., 2 * half:]], axis=-1)
    return out


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """HF-convention rotary embedding (rotate-half). x: [B, T, Hx, hd]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    return _rotate(x, positions, freqs)


def _rope_kind(x: jax.Array, positions: jax.Array,
               rope: Dict[str, Any]) -> jax.Array:
    """The rope of a table's attention kind (:func:`rope_frequencies`)."""
    freqs, scale = rope_frequencies(rope, x.shape[-1])
    return _rotate(x, positions, freqs, scale,
                   interleave=bool(rope.get("interleave")))


def _mm(x: jax.Array, w: Any) -> jax.Array:
    """Matmul against a possibly-quantized weight leaf.

    Plain arrays take the literal ``x @ w`` — the default (bf16) path
    traces the exact pre-quant jaxpr, byte-identical outputs. Quantized
    ``{"q", "s"}`` leaves matmul the 1-byte weights (cast fuses into the
    MXU feed, so only the int8/fp8 bytes move from HBM) and apply the
    per-output-channel scale to the product — exact, because the scale is
    constant along the contraction axis."""
    if isinstance(w, dict):
        y = x @ w["q"].astype(x.dtype)
        return (y.astype(jnp.float32) * w["s"][0]).astype(x.dtype)
    return x @ w


class _LayerSlice:
    """Static per-layer slice of the stacked param tree; quantized
    ``{"q", "s"}`` leaves slice both members. The slice is taken where the
    weight is read, so it carries that stage's scope. XLA fuses it into the
    matmul that reads it (a read, not a copy) unless it re-lays the weight
    for that matmul, which is what :func:`_qkv_proj` keeps from
    happening."""

    def __init__(self, stacked: Dict[str, Any], li: int):
        self._stacked, self._li = stacked, li

    def __getitem__(self, name: str) -> Any:
        w, li = self._stacked[name], self._li
        if isinstance(w, dict):
            return {k: v[li] for k, v in w.items()}
        return w[li]


class _TableSlice:
    """:class:`_LayerSlice` of a table's tree (``_init_table_params``):
    layer ``li``'s row of whichever stack holds ``name`` for it."""

    _FFN = {"dense": ("w_gate", "w_up", "w_down"),
            "sparse": ("w_router", "router_bias", "shared_gate", "shared_up",
                       "shared_down") + EXPERT_LEAVES}

    def __init__(self, stacked: Dict[str, Any], li: int, entry, kind,
                 kv_at: int):
        self._stacked, self._li = stacked, li
        self._entry, self._kind, self._kv_at = entry, kind, kv_at

    def __getitem__(self, name: str) -> Any:
        w = self._stacked[name]
        if name in ("wq", "wo", "w_attn_gate", "q_norm"):
            return w[self._kind.name][self._entry.attn_at]
        if name.startswith(("kda_", "mla_", "gdn_")):  # a kind's own leaves
            return w[self._entry.attn_at]
        if name in ("wk", "wv", "k_norm"):
            return w[self._kv_at]
        if name in self._FFN[self._entry.ffn]:
            return w[self._entry.ffn_at]
        return w[self._li]

    def get(self, name: str) -> Any:
        return self[name] if name in self._stacked else None


def layer_params(cfg: ModelConfig, stacked: Dict[str, Any], li: int):
    """(slice, table row, attention kind) of layer ``li``."""
    entry = cfg.layer_table[li]
    kind = cfg.attn_kinds[entry.attn]
    if cfg.has_table:
        return _TableSlice(stacked, li, entry, kind,
                           kv_layer_index(cfg, li)), entry, kind
    return _LayerSlice(stacked, li), entry, kind


def kv_layer_index(cfg: ModelConfig, li: int) -> int:
    """Layer ``li``'s place among the layers that keep K and V pages: its
    row of ``wk`` / ``wv`` and of the cache's ``"k"`` / ``"v"`` lists."""
    if not cfg.has_table:
        return li
    return sum(t in KV_KINDS for t in cfg.layer_types[:li])


def _qkv_proj(x: jax.Array, p: Any, H: int, KV: int, hd: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``x [B, T, D]`` times a layer's ``wq`` / ``wk`` / ``wv``, split into
    heads: ``q [B, T, H, hd]``, ``k`` and ``v`` ``[B, T, KV, hd]``.

    The barrier stands between the matmuls and the reshape. Without it
    XLA's TPU pipeline folds the head reshape of the two products that feed
    :func:`_rope` into their dot: a convolution over ``H`` whose result is
    head-major ``[H, B, hd]``. For that it wants the weight as ``[H, hd,
    D]`` with ``D`` minor; the parameter arrives ``[D, H*hd]``, a
    parameter's layout is fixed, so every step program slices and
    physically transposes every ``wq[l]`` and ``wk[l]`` (and on a
    ``--mesh 1,4`` shard ``wv[l]``) before it multiplies by them:
    ``slice_bitcast_fusion`` + ``copy``, 3.4 of a 16.5 ms decode step at
    Mistral-7B widths on a v5e (PERF.md, PR 31). Behind the barrier the
    three stay the plain ``[B*T, D] x [D, N]`` matmuls that ``wo`` and the
    MLP are, read their weight slice inside the fusion at the HBM's rate,
    and the compiler asks for no other layout. The price is the products'
    own round trip through HBM, 0.6 MB a decode step. Same arithmetic,
    same bits. ``tests/test_chip_compile.py`` holds the compiled step
    programs to "no relayout of a weight"."""
    B, T = x.shape[:2]
    q, k, v = jax.lax.optimization_barrier(
        (_mm(x, p["wq"]), _mm(x, p["wk"]), _mm(x, p["wv"])))
    return (q.reshape(B, T, H, hd), k.reshape(B, T, KV, hd),
            v.reshape(B, T, KV, hd))


def _dequant_leaf(w: Any, dtype) -> jax.Array:
    """Full dequantization for consumers that need a plain array (MoE
    expert dispatch)."""
    if isinstance(w, dict):
        return (w["q"].astype(jnp.float32) * w["s"]).astype(dtype)
    return w


_Q_BLOCK = 512  # query-block size for long prefill chunks: caps the f32
                # score tensor at [B, H, _Q_BLOCK, S] — an unblocked
                # 4096-token chunk against 8k context materialises 4.3 GB
                # of scores and OOMs next to a serving-sized KV cache


def _attention(
    q: jax.Array,        # [B, T, H, hd]
    k_all: jax.Array,    # [B, S, KV, hd]  gathered sequence KV
    v_all: jax.Array,    # [B, S, KV, hd]
    positions: jax.Array,  # [B, T] absolute positions (-1 = pad)
    window: int = 0,       # > 0: a query sees only the last ``window`` keys
) -> jax.Array:
    T = q.shape[1]
    if T > _Q_BLOCK:
        outs = [
            _attention(q[:, t0:t0 + _Q_BLOCK], k_all, v_all,
                       positions[:, t0:t0 + _Q_BLOCK], window)
            for t0 in range(0, T, _Q_BLOCK)
        ]
        return jnp.concatenate(outs, axis=1)
    B, T, H, hd = q.shape
    S, KV = k_all.shape[1], k_all.shape[2]
    G = H // KV
    # bf16 inputs, f32 MXU accumulation — an .astype(f32) on the gathered
    # context would materialise it twice over in HBM
    scores = jnp.einsum(
        "btkgh,bskh->btkgs", q.reshape(B, T, KV, G, hd), k_all,
        preferred_element_type=jnp.float32,
    ) / np.sqrt(hd)
    # causal paged mask: key slot s corresponds to absolute position s
    kpos = jnp.arange(S)[None, None, :]                  # [1, 1, S]
    valid = kpos <= positions[:, :, None]                # [B, T, S]
    if window:
        valid = valid & (kpos > positions[:, :, None] - window)
    scores = jnp.where(valid[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "btkgs,bskh->btkgh", probs.astype(q.dtype), v_all,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, T, H, hd).astype(q.dtype)


def attention_class(eng: EngineConfig, T: int) -> str:
    """Shape class of a ``[B, T]`` chunk: decode / spec / prefill.

    T is static at trace time, so the class (and the impl picked from it)
    is baked into each compiled step function.
    """
    if T == 1:
        return "decode"
    if eng.spec_mode != "off" and T <= eng.spec_k + 1:
        return "spec"
    return "prefill"


def resolve_attention_impl(eng: EngineConfig, attn_class: str) -> str:
    """Resolve the attention impl ("pallas" | "einsum") for a shape class.

    Decode follows ``attention_impl``.  The T>1 classes run einsum unless
    ``attention_impl_{spec,prefill}`` says otherwise — running every CPU
    test's prefills through interpret-mode Pallas would be pointlessly
    slow, and no deployment selects the kernel there yet.
    """
    if attn_class == "decode":
        return eng.attention_impl
    return getattr(eng, f"attention_impl_{attn_class}") or "einsum"


def _walk_tile(eng: EngineConfig, mesh: Optional[Mesh],
               kv_heads: int, head_dim: int, page_dtype) -> int:
    """Key positions one step of the kernel's KV walk covers, as the kernel
    resolves it for the shapes a launch sees: under ``shard_map`` a shard's
    share of the KV heads."""
    from ..ops.paged_attention import default_kv_tile

    tp = mesh.shape.get(AXIS_TP, 1) if mesh is not None else 1
    return default_kv_tile(eng.block_size, max(1, kv_heads // tp), head_dim,
                           page_dtype)


def _config_kv_tile(cfg: ModelConfig, eng: EngineConfig,
                    mesh: Optional[Mesh]) -> int:
    """``_walk_tile`` of the cache this configuration builds."""
    page_dtype = quant.storage_dtype(eng.kv_dtype) \
        if quant.is_quantized(eng.kv_dtype) else _dtype(cfg)
    if "kv" not in cfg.cache_kinds and cfg.has_latent_cache:
        # the only pages are latent ones: one "KV head" a token wide
        return _walk_tile(eng, mesh, 1, latent_width(cfg), page_dtype)
    return _walk_tile(eng, mesh, cfg.num_kv_heads, cfg.head_dim_, page_dtype)


def decode_kv_tile(cfg: ModelConfig, eng: EngineConfig,
                   mesh: Optional[Mesh]) -> int:
    """Key positions one step of the decode kernel's KV walk covers in the
    decode window as it is traced (``ATTENTION_TRACES["decode"]["tile"]``);
    0 when the decode class runs the einsum path, which has no walk.  The
    host's ``StepRecord.kv_blocks_walked`` counts with it."""
    if resolve_attention_impl(eng, "decode") != "pallas":
        return 0
    return _config_kv_tile(cfg, eng, mesh)


def attention_choice(cfg: ModelConfig, eng: EngineConfig,
                     mesh: Optional[Mesh]) -> Dict[str, Any]:
    """Per shape class, the impl and the ``[q_tile, kv_tile]`` its step
    programs are traced with, as ``ATTENTION_TRACES`` notes them: the
    kernel's decode window is ``[1, kv_tile]``, a spec or prefill launch
    ``[0, kv_tile]`` (the kernel's own q tile for each T), einsum
    ``[0, 0]``.  Decided from the configuration and the mesh alone."""
    impls = {cls: resolve_attention_impl(eng, cls)
             for cls in ("decode", "spec", "prefill")}
    kv_tile = _config_kv_tile(cfg, eng, mesh)
    choice = {
        "impl": impls,
        "tiles": {cls: [int(cls == "decode"), kv_tile]
                  if impl == "pallas" else [0, 0]
                  for cls, impl in impls.items()},
    }
    if cfg.has_latent_cache:
        # a latent layer: decode absorbed (through the kernel where decode
        # runs it, else over the gathered table), T > 1 expanded over the
        # gathered table whatever the K / V layers run: key tile by key tile
        # where the platform compiles kernels (latent_chunk_tiles; "tiles":
        # the largest chunk's over the widest table), else the einsum
        S = eng.max_blocks_per_seq * eng.block_size
        chunk = {cls: latent_chunk_tiles(mesh, T, S) for cls, T in (
            ("spec", eng.spec_k + 1), ("prefill", max(eng.prefill_buckets)))}
        choice["latent"] = {
            "decode": f"{impls['decode']}-absorbed",
            **{cls: "pallas-tiled-expanded" if tiles else "einsum-expanded"
               for cls, tiles in chunk.items()}}
        if chunk["prefill"]:
            choice["latent"]["tiles"] = list(chunk["prefill"])
    if cfg.has_seat_state:
        # over the seat pool: a kernel where decode runs them; a chunk: XLA
        kern = impls["decode"] == "pallas"
        choice["linear"] = {
            "decode": ("pallas" if kern else "xla") + "-recurrent",
            "prefill": "xla-chunked", "conv": "xla-gather"}
        if cfg.gated_delta:  # its decode step's conv tails: a kernel too
            choice["linear"].update(rule="gated-delta", conv=(
                "pallas-seats" if kern else "xla-gather"))
    return choice


# What each attention shape class resolved to the last time a step program
# was TRACED in this process: impl, whether the Pallas kernel was handed to
# the interpreter, and the tiles.  Process-wide (one serving engine per
# process); ``InferenceEngine.device_report`` and ``chip_smoke.py`` read it
# to assert that the chip ran the compiled kernel it was asked for.
ATTENTION_TRACES: Dict[str, Dict[str, Any]] = {}


def pallas_interpret(mesh: Optional[Mesh]) -> bool:
    """Whether Pallas kernels of a step on ``mesh`` are interpreted.

    Exactly one platform interprets — the CPU, where the tests run; a TPU
    compiles the kernel, always; anything else has no Pallas path here and
    says so instead of quietly interpreting."""
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas attention kernel targets TPU (interpreted on CPU for "
        f"tests); platform {platform!r} has neither"
    )


def _note_attention(attn_class: str, impl: str, interpret: bool,
                    tile: Tuple[int, int]) -> None:
    ATTENTION_TRACES[attn_class] = {
        "impl": impl, "interpret": interpret, "tile": list(tile),
    }


def latent_chunk_tiles(mesh: Optional[Mesh], T: int,
                       S: int) -> Optional[Tuple[int, int]]:
    """``(q_tile, kv_tile)`` of the kernel a latent layer's ``[T]`` chunk
    over ``S`` gathered keys runs (``ops/latent_chunk_attention.py``), or
    None where it runs :func:`latent_attention`'s einsum: a decode step
    (absorbed), the CPU (the einsum is the tests' oracle, and an
    interpreted kernel in every test's prefill would be pointlessly slow),
    and the shapes the kernel leaves to it (a spec window's few rows, a
    table under a lane tile of keys).  The one place that decides."""
    from ..ops.latent_chunk_attention import chunk_tiles

    if T == 1 or pallas_interpret(mesh):
        return None
    return chunk_tiles(T, S)


def _paged_decode_attention(
    eng: EngineConfig,
    mesh: Optional[Mesh],
    q: jax.Array,            # [B, 1, H, hd]
    lk: jax.Array,           # [NB, KV, bs, hd] this layer's cache (updated)
    lv: jax.Array,           # [NB, KV, bs, hd]
    block_tables: jax.Array,  # [B, W]
    seq_lens: jax.Array,      # [B] valid context incl. current token
    lks: Optional[jax.Array] = None,  # [NB, KV, bs] f32 scales (quant kv)
    lvs: Optional[jax.Array] = None,
    window: int = 0,          # the layer's window: the walk's lower bound
) -> jax.Array:
    """Decode-path attention via the Pallas paged kernel ([B, 1, H, hd]).

    When the cache is head-sharded over ``tp`` the kernel runs under
    ``shard_map`` so each shard streams only its own KV heads — a bare
    pallas_call is opaque to the GSPMD partitioner and would force an
    all-gather of the whole cache.
    """
    from ..ops.paged_attention import paged_attention_decode

    interpret = pallas_interpret(mesh)
    kv_tile = _walk_tile(eng, mesh, lk.shape[1], lk.shape[3], lk.dtype)
    _note_attention("decode", "pallas", interpret, (1, kv_tile))
    kernel = functools.partial(
        paged_attention_decode,
        block_size=eng.block_size,
        kv_tile=kv_tile,
        interpret=interpret,
        window=window,
    )
    q3 = q[:, 0]  # [B, H, hd]
    if mesh is not None and mesh.shape.get(AXIS_TP, 1) > 1:
        lay = SpecLayout.for_mesh(mesh)
        heads = layout.spec(None, lay.tp, None)
        if lks is not None:
            out = layout.shard_map(
                lambda q_, k_, v_, t_, s_, ks_, vs_: kernel(
                    q_, k_, v_, t_, s_, k_scale=ks_, v_scale=vs_
                ),
                mesh=mesh,
                in_specs=(
                    heads, lay.cache_block(), lay.cache_block(),
                    layout.spec(None, None), layout.spec(None),
                    lay.cache_scale_block(), lay.cache_scale_block(),
                ),
                out_specs=heads,
            )(q3, lk, lv, block_tables, seq_lens, lks, lvs)
        else:
            out = layout.shard_map(
                lambda q_, k_, v_, t_, s_: kernel(q_, k_, v_, t_, s_),
                mesh=mesh,
                in_specs=(
                    heads, lay.cache_block(), lay.cache_block(),
                    layout.spec(None, None), layout.spec(None),
                ),
                out_specs=heads,
            )(q3, lk, lv, block_tables, seq_lens)
    else:
        out = kernel(q3, lk, lv, block_tables, seq_lens,
                     k_scale=lks, v_scale=lvs)
    return out[:, None]


def _paged_ragged_attention(
    eng: EngineConfig,
    mesh: Optional[Mesh],
    q: jax.Array,             # [B, T, H, hd]
    lk: jax.Array,            # [NB, KV, bs, hd] this layer's cache (updated)
    lv: jax.Array,            # [NB, KV, bs, hd]
    block_tables: jax.Array,  # [B, W]
    q_len: jax.Array,         # [B] valid (prefix) queries per row, 0 = dead
    ctx_len: jax.Array,       # [B] context incl. the row's own tokens
    lks: Optional[jax.Array] = None,  # [NB, KV, bs] f32 scales (quant kv)
    lvs: Optional[jax.Array] = None,
    window: int = 0,
) -> jax.Array:
    """T>1 attention (spec windows, prefill chunks) via the ragged kernel.

    Rows pack flat with stride T (``q_start = arange(B+1) * T``); the
    forward contract guarantees valid tokens are a per-row prefix, which
    is exactly the ragged layout.  Sharding story mirrors
    ``_paged_decode_attention``.
    """
    from ..ops.paged_attention import paged_attention_ragged

    B, T, H, hd = q.shape
    interpret = pallas_interpret(mesh)
    attn_class = attention_class(eng, T)
    kv_tile = _walk_tile(eng, mesh, lk.shape[1], lk.shape[3], lk.dtype)
    # q tile 0: the kernel's own for this T
    _note_attention(attn_class, "pallas", interpret, (0, kv_tile))
    kernel = functools.partial(
        paged_attention_ragged,
        block_size=eng.block_size,
        max_q_len=T,
        kv_tile=kv_tile,
        interpret=interpret,
        window=window,
    )
    q_flat = q.reshape(B * T, H, hd)
    q_start = jnp.arange(B + 1, dtype=jnp.int32) * T
    if mesh is not None and mesh.shape.get(AXIS_TP, 1) > 1:
        lay = SpecLayout.for_mesh(mesh)
        heads = layout.spec(None, lay.tp, None)
        if lks is not None:
            out = layout.shard_map(
                lambda q_, k_, v_, t_, s_, ql_, cl_, ks_, vs_: kernel(
                    q_, k_, v_, t_, s_, ql_, cl_,
                    k_scale=ks_, v_scale=vs_,
                ),
                mesh=mesh,
                in_specs=(
                    heads, lay.cache_block(), lay.cache_block(),
                    layout.spec(None, None), layout.spec(None),
                    layout.spec(None), layout.spec(None),
                    lay.cache_scale_block(), lay.cache_scale_block(),
                ),
                out_specs=heads,
            )(q_flat, lk, lv, block_tables, q_start, q_len, ctx_len,
              lks, lvs)
        else:
            out = layout.shard_map(
                lambda q_, k_, v_, t_, s_, ql_, cl_: kernel(
                    q_, k_, v_, t_, s_, ql_, cl_
                ),
                mesh=mesh,
                in_specs=(
                    heads, lay.cache_block(), lay.cache_block(),
                    layout.spec(None, None), layout.spec(None),
                    layout.spec(None), layout.spec(None),
                ),
                out_specs=heads,
            )(q_flat, lk, lv, block_tables, q_start, q_len, ctx_len)
    else:
        out = kernel(q_flat, lk, lv, block_tables, q_start, q_len, ctx_len,
                     k_scale=lks, v_scale=lvs)
    return out.reshape(B, T, H, hd)


def check_feed_positions(positions: np.ndarray) -> None:
    """Hold a host-built ``[B, T]`` feed to ``forward``'s contract: each
    row's valid positions are a prefix, contiguous from its first. Host
    integers only."""
    valid = positions >= 0
    n = valid.sum(axis=1, keepdims=True)
    idx = np.arange(positions.shape[1])[None, :]
    want = np.where(idx < n, positions[:, :1] + idx, -1)
    assert np.array_equal(positions, want), (
        "feed rows must hold contiguous positions as a prefix")


def _kv_pages(positions: jax.Array, block_tables: jax.Array,
              bs: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Where a step's ``[B, T]`` chunk lands in the paged cache, page by
    page: ``(pages [B, P], shift [B], mask [B, P*bs])``, shared by every
    layer and plane of the step (:func:`_kv_write`).

    A row's valid tokens are a prefix at contiguous positions ``start + t``
    (``forward``'s contract), so they lie in at most ``P = (T + bs - 2) //
    bs + 1`` logical blocks from ``start // bs`` on: 1 at T=1, 17 at T=256.
    ``pages`` are those blocks' physical ids; an entry no valid token falls
    on (a dead row, the pad tail, past the row's last page) is redirected
    to block 0. ``shift = start % bs`` is the slot of the row's first token
    in its first page, ``mask`` the slots of the ``P`` pages that take a
    token."""
    T = positions.shape[1]
    W = block_tables.shape[1]
    P = (T + bs - 2) // bs + 1
    n = jnp.sum(positions >= 0, axis=1).astype(jnp.int32)       # [B]
    start = jnp.maximum(positions[:, 0], 0)
    shift = start % bs
    end = (shift + n)[:, None]
    page = jnp.arange(P, dtype=jnp.int32)[None, :]
    slot = jnp.arange(P * bs, dtype=jnp.int32)[None, :]
    phys = jnp.take_along_axis(
        block_tables, jnp.minimum(start[:, None] // bs + page, W - 1), axis=1)
    pages = jnp.where((page * bs < end) & (n[:, None] > 0), phys, 0)
    mask = (slot >= shift[:, None]) & (slot < end)
    return pages, shift, mask


def _kv_write(plane: jax.Array, pages: jax.Array, shift: jax.Array,
              mask: jax.Array, upd: jax.Array,
              mesh: Optional[Mesh] = None) -> jax.Array:
    """Write one step's rows into a cache plane, whole pages at a time; on
    every real block the same bits as ``plane.at[blocks, :, offs]
    .set(upd)``, without the whole-plane layout copies that expression
    costs on the TPU (module header).

    ``plane`` is a payload plane ``[NB, KV, bs, hd]`` with ``upd [B, T, KV,
    hd]``, or a scale plane ``[NB, KV, bs]`` with ``upd [B, T, KV]``;
    ``pages`` / ``shift`` / ``mask`` are :func:`_kv_pages` of the step.

    Read the ``B*P`` pages (a gather on dim 0, the page its window), lay
    each row's updates into page shape (a pad and one ``dynamic_slice`` a
    row shift them by ``shift``; at T=1 the one update is every slot's and
    the mask picks its own: 0.53 against the slice's 0.89 ms at 64 rows,
    PERF.md, PR 33), ``where(mask, new, old)``, and scatter the
    merged pages back on dim 0 alone: as many updates as pages touched,
    where one per (token, head) cost ~70 ns each whatever its size
    (PERF.md, PR 33). The indexed dim leads, so the scatter takes the plane
    row-major and nothing is converted. A redirected entry writes block
    0's own bytes back: block 0 is never modified. On a ``tp`` mesh the
    write runs under ``shard_map`` with the cache's own specs, as the
    kernel does (a device's page is its ``KV / tp`` heads)."""
    def write(plane_, pages_, shift_, mask_, upd_):
        KV, bs = plane_.shape[1:3]
        tail = plane_.shape[3:]
        B, T = upd_.shape[:2]
        P = pages_.shape[1]
        if T == 1:
            rows = jnp.broadcast_to(upd_, (B, bs) + upd_.shape[2:])
        else:
            padded = jnp.pad(upd_, ((0, 0), (bs - 1, P * bs - T))
                             + ((0, 0),) * (upd_.ndim - 2))
            rows = jax.vmap(
                lambda r, s: jax.lax.dynamic_slice_in_dim(
                    r, bs - 1 - s, P * bs))(padded, shift_)
        # [B, P*bs, KV, ...] -> [B*P, KV, bs, ...]
        new = jnp.swapaxes(rows.reshape((B * P, bs, KV) + tail), 1, 2)
        take = mask_.reshape((B * P, 1, bs) + (1,) * len(tail))
        ids = pages_.reshape(-1)
        old = jnp.take(plane_, ids, axis=0)
        return plane_.at[ids].set(jnp.where(take, new, old))

    if mesh is None or mesh.shape.get(AXIS_TP, 1) == 1:
        return write(plane, pages, shift, mask, upd)
    lay = SpecLayout.for_mesh(mesh)
    plane_spec = (lay.cache_block() if plane.ndim == 4
                  else lay.cache_scale_block())
    upd_spec = layout.spec(None, None, lay.tp, *(None,) * (upd.ndim - 3))
    return layout.shard_map(
        write, mesh=mesh,
        in_specs=(plane_spec, layout.spec(None, None), layout.spec(None),
                  layout.spec(None, None), upd_spec),
        out_specs=plane_spec,
    )(plane, pages, shift, mask, upd)


def _layer_attention(
    eng: EngineConfig, mesh, ring_mesh, ring_lay, use_pallas: bool,
    q, k, v, lk, lv, lks, lvs, positions, block_tables,
    seq_lens, q_len, ctx_len, window: int = 0,
) -> jax.Array:
    """One layer's attention over the just-updated paged cache: the ring
    (full fresh prompt, T-sharded), the Pallas decode / ragged kernel, or
    the gathered-context einsum. Returns ``[B, T, H, hd]``.  ``window`` is
    the layer's (0 = none): a mask on the einsum path, a mask and the
    walk's lower bound in the kernel; the ring has neither."""
    B, T = q.shape[:2]
    KV, bs, hd = lk.shape[1], lk.shape[2], lk.shape[3]
    W = block_tables.shape[1]
    if ring_lay is not None:
        from ..parallel.ring_attention import ring_attention

        # the ring runs over the serving mesh itself — the sequence
        # axis is the composite (dp, tp) [..fsdp] axes, so the K/V the
        # scatter reshards into the head-sharded cache never crosses a
        # mesh boundary (THE involuntary-remat source this replaces)
        seq_spec = ring_lay.heads_seq()
        return layout.shard_map(
            functools.partial(
                ring_attention, axis_name=ring_lay.seq_axes()
            ),
            mesh=ring_mesh,
            in_specs=(seq_spec, seq_spec, seq_spec),
            out_specs=seq_spec,
        )(q, k, v)
    if use_pallas and T == 1:
        return _paged_decode_attention(
            eng, mesh, q, lk, lv, block_tables, seq_lens,
            lks=lks, lvs=lvs, window=window,
        )
    if use_pallas:
        return _paged_ragged_attention(
            eng, mesh, q, lk, lv, block_tables, q_len, ctx_len,
            lks=lks, lvs=lvs, window=window,
        )
    # gather the full context for attention: [B, W*bs, KV, hd] with
    # gathered position = w*bs + offset = absolute position
    k_all = jnp.take(
        lk, block_tables.reshape(-1), axis=0
    ).reshape(B, W, KV, bs, hd).transpose(0, 1, 3, 2, 4).reshape(
        B, W * bs, KV, hd
    )
    v_all = jnp.take(
        lv, block_tables.reshape(-1), axis=0
    ).reshape(B, W, KV, bs, hd).transpose(0, 1, 3, 2, 4).reshape(
        B, W * bs, KV, hd
    )
    if lks is not None:
        ks_all = jnp.take(
            lks, block_tables.reshape(-1), axis=0
        ).reshape(B, W, KV, bs).transpose(0, 1, 3, 2).reshape(
            B, W * bs, KV
        )
        vs_all = jnp.take(
            lvs, block_tables.reshape(-1), axis=0
        ).reshape(B, W, KV, bs).transpose(0, 1, 3, 2).reshape(
            B, W * bs, KV
        )
        k_all = quant.kv_dequantize(k_all, ks_all, q.dtype)
        v_all = quant.kv_dequantize(v_all, vs_all, q.dtype)
    return _attention(q, k_all, v_all, positions, window)


# The layer body, in three parts around the attention itself (which differs
# by caller: the paged cache here, the chunk's own K and V in
# encode_forward, a stage's stacked cache in parallel/pp_serving.py).  Every
# caller runs these, so a layer kind is written once.


def latent_inputs(cfg: ModelConfig, kind, p: Any, h: jax.Array,
                  positions: jax.Array):
    """A latent (MLA) layer's normed input ``x``, the queries' two parts
    ``q_nope [B, T, H, qk_nope]`` and the roped ``q_pe [B, T, H, qk_rope]``,
    and what the token keeps: ``[B, T, 1, latent_width]`` = the normed
    latent ``c``, the roped key part that all heads share, zeros.  With
    ``q_lora_rank`` the queries are ``rmsnorm(x Wqa) Wqb`` (``wq`` is
    ``Wqb``); ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` scale them and
    ``c`` by ``sqrt(hidden_size / rank)``, so the page holds the scaled
    ``c``."""
    B, T, _ = h.shape
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("qkv_proj"):
        x = _rms_norm(h, p["attn_norm"], cfg.rms_norm_eps)
        if cfg.q_lora_rank:
            cq, ckpe = jax.lax.optimization_barrier(
                (_mm(x, p["mla_wqa"]), _mm(x, p["mla_wdkv"])))
            q = _mm(_rms_norm(cq, p["mla_q_norm"], cfg.rms_norm_eps),
                    p["wq"])
            if cfg.mla_scale_q_lora:
                q = q * (cfg.hidden_size / cfg.q_lora_rank) ** 0.5
        else:
            q, ckpe = jax.lax.optimization_barrier(
                (_mm(x, p["wq"]), _mm(x, p["mla_wdkv"])))
        q = q.reshape(B, T, kind.num_heads, dn + dr)
        c_norm = p["mla_kv_norm"]
        if cfg.mla_scale_kv_lora:
            # the scale rides the norm's float32 weight: one rounding
            c_norm = (c_norm.astype(jnp.float32)
                      * (cfg.hidden_size / r) ** 0.5)
        c = _rms_norm(ckpe[..., :r], c_norm, cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        rope = cfg.rope_of(kind)
        q_pe = _rope_kind(q[..., dn:], positions, rope)
        k_pe = _rope_kind(ckpe[:, :, None, r:], positions, rope)
    latent = jnp.concatenate([c[:, :, None, :], k_pe], axis=-1)
    latent = jnp.pad(latent, ((0, 0),) * 3
                     + ((0, latent_width(cfg) - r - dr),))
    return x, q[..., :dn], q_pe, latent


def _latent_up(cfg: ModelConfig, p: Any, heads: int):
    """``Wukv`` of a latent layer as its two halves: ``[r, H, qk_nope]``
    that makes a latent a head's key, ``[r, H, v_head_dim]`` its value."""
    w = p["mla_wukv"].reshape(cfg.kv_lora_rank, heads,
                              cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def latent_attention(cfg: ModelConfig, p: Any, q_nope: jax.Array,
                     q_pe: jax.Array, ctx: jax.Array, positions: jax.Array,
                     absorbed: bool) -> jax.Array:
    """Causal attention of ``q_nope`` / ``q_pe`` ``[B, T, H, .]`` over the
    latents ``ctx [B, S, latent_width]`` of positions ``0 .. S - 1``;
    returns ``[B, T, H, v_head_dim]``.

    Expanded (a prefill chunk): every latent is multiplied out to its
    heads' keys and values, ``k_nope | v = c Wukv``.  Absorbed (decode):
    the query is taken to the latent instead, ``q_nope Wuk^T``, the scores
    and the weighted sum run against ``c`` itself, all heads over the one
    vector a token, and only the result goes through ``Wuv``: a token's 576
    values are read once, not multiplied out ``H`` times.  Same
    mathematics; the Pallas decode path (:func:`_paged_latent_decode`) is
    the absorbed form over pages."""
    B, T, H, dn = q_nope.shape
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    S = ctx.shape[1]
    wuk, wuv = _latent_up(cfg, p, H)
    c, k_pe = ctx[..., :r], ctx[..., r:r + dr]
    f32 = dict(preferred_element_type=jnp.float32)
    scores = jnp.einsum("bthd,bsd->bhts", q_pe, k_pe, **f32)
    if absorbed:
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, wuk,
                           **f32).astype(ctx.dtype)
        scores = scores + jnp.einsum("bthr,bsr->bhts", q_lat, c, **f32)
    else:
        k_nope = jnp.einsum("bsr,rhd->bshd", c, wuk, **f32).astype(ctx.dtype)
        scores = scores + jnp.einsum("bthd,bshd->bhts", q_nope, k_nope,
                                     **f32)
    scores = scores * (dn + dr) ** -0.5
    valid = jnp.arange(S)[None, None, :] <= positions[:, :, None]  # [B,T,S]
    probs = jax.nn.softmax(jnp.where(valid[:, None], scores, -1e30), axis=-1)
    probs = probs.astype(ctx.dtype)
    if absorbed:
        o_lat = jnp.einsum("bhts,bsr->bthr", probs, c, **f32)
        out = jnp.einsum("bthr,rhd->bthd", o_lat.astype(ctx.dtype), wuv,
                         **f32)
    else:
        v = jnp.einsum("bsr,rhd->bshd", c, wuv, **f32).astype(ctx.dtype)
        out = jnp.einsum("bhts,bshd->bthd", probs, v, **f32)
    return out.astype(q_nope.dtype)


def _latent_chunk(cfg: ModelConfig, mesh, p: Any, q_nope, q_pe, ctx,
                  positions, tiles: Tuple[int, int]):
    """:func:`latent_attention`'s expanded form through the tiled kernel:
    no ``[H, T, S]`` array in memory, and keys walked as far as the chunk's
    context and no further."""
    from ..ops.latent_chunk_attention import latent_chunk_attention

    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    interpret = pallas_interpret(mesh)
    _note_attention("latent_prefill", "pallas-tiled-expanded", interpret,
                    tiles)
    return latent_chunk_attention(
        q_nope, q_pe, ctx, p["mla_wukv"], positions, rank=cfg.kv_lora_rank,
        rope=dr, scale=float((dn + dr) ** -0.5), tiles=tiles,
        interpret=interpret)


def _paged_latent_decode(cfg: ModelConfig, eng: EngineConfig, mesh, p: Any,
                         q_nope, q_pe, plane, block_tables, seq_lens):
    """The absorbed decode step through the Pallas paged kernel: ``H``
    query heads over the one latent "KV head", the page at once key (all of
    it) and value (its first ``kv_lora_rank`` dims), walked as far as each
    row's context and no further."""
    from ..ops.paged_attention import paged_attention_decode

    B, _, H, dn = q_nope.shape
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    wuk, wuv = _latent_up(cfg, p, H)
    f32 = dict(preferred_element_type=jnp.float32)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wuk,
                       **f32).astype(plane.dtype)
    q_abs = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)
    q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, plane.shape[-1] - r - dr)))
    interpret = pallas_interpret(mesh)
    kv_tile = _walk_tile(eng, mesh, 1, plane.shape[-1], plane.dtype)
    _note_attention("decode", "pallas", interpret, (1, kv_tile))
    o_lat = paged_attention_decode(
        q_abs, plane, plane, block_tables, seq_lens,
        block_size=eng.block_size, kv_tile=kv_tile, interpret=interpret,
        v_width=r, scale=float((dn + dr) ** -0.5))
    return jnp.einsum("bhr,rhd->bhd", o_lat, wuv,
                      **f32).astype(q_nope.dtype)[:, None]


def linear_attention(cfg: ModelConfig, kind, p: Any, h: jax.Array,
                     positions: jax.Array, seats: jax.Array,
                     state: jax.Array, conv: jax.Array,
                     kernel: Optional[bool] = None):
    """One linear-attention (KDA) layer up to its output norm: the normed
    input ``x``, ``o [B, T, H, hd]``, and the layer's state pools with the
    rows' seats advanced (``ops/delta_rule.py`` has the mathematics).

    A row reads its seat ``seats [B]``: the state ``[H, hd, hd]`` and the
    convolution's last inputs.  A row whose chunk starts at position 0
    starts from zeros instead, whatever an earlier holder left in the seat:
    a new request, and a preempted one that is recomputed from its first
    token.  Pads (position -1) bring decay 1 and step size 0, so a row's
    pad tail, a dead row and the trash seat's pad rows put back what they
    read.

    ``kernel`` (None: the XLA form; else whether the kernel is interpreted):
    the decode step's recurrence runs as the Pallas kernel over the pool
    itself (``delta_rule.kda_step_seats``), where the decode class runs its
    kernels at all and the pool is float32."""
    from ..ops import delta_rule as dr

    B, T, _ = h.shape
    H, hd = kind.num_heads, cfg.head_dim_
    valid = positions >= 0
    n = jnp.sum(valid, axis=1).astype(jnp.int32)
    fresh = positions[:, 0] == 0
    with jax.named_scope("kda_proj"):
        x = _rms_norm(h, p["attn_norm"], cfg.rms_norm_eps)
        q, k, v, f, b = jax.lax.optimization_barrier(
            (_mm(x, p["wq"]), _mm(x, p["kda_wk"]), _mm(x, p["kda_wv"]),
             _mm(x, p["kda_wf"]), _mm(x, p["kda_w_beta"])))
    with jax.named_scope("kda_conv"):
        prev = jnp.where(fresh[:, None, None], 0,
                         jnp.take(conv, seats, axis=0))
        y, nxt = dr.short_conv(jnp.concatenate([q, k, v], axis=-1), prev,
                               p["kda_conv"], n)
        conv = conv.at[seats].set(nxt.astype(conv.dtype))
        y = jax.nn.silu(y).reshape(B, T, 3, H, hd)
        q = dr.l2norm(y[:, :, 0]) * hd ** -0.5
        k = dr.l2norm(y[:, :, 1])
        v = y[:, :, 2]
        rate = jnp.exp(p["kda_a_log"].astype(jnp.float32))[:, None]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            rate * (f.astype(jnp.float32) + p["kda_dt_bias"]
                    ).reshape(B, T, H, hd))
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None],
                         jax.nn.sigmoid(b.astype(jnp.float32)), 0.0)
    with jax.named_scope("kda_recurrent" if T == 1 else "kda_chunk"):
        if T == 1 and kernel is not None and state.dtype == jnp.float32:
            o, state = dr.kda_step_seats(
                state, seats, fresh, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                beta[:, 0], interpret=kernel)
            o = o[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0,
                           jnp.take(state, seats, axis=0)
                           ).astype(jnp.float32)
            if T == 1:
                o, s1 = dr.kda_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0])
                o = o[:, None]
            else:
                o, s1 = dr.kda_chunked(s0, q, k, v, g, beta)
            state = state.at[seats].set(s1.astype(state.dtype))
    with jax.named_scope("kda_out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = (o * p["kda_o_norm"].astype(jnp.float32)).astype(h.dtype)
    return x, o, state, conv


def gated_delta_attention(cfg: ModelConfig, kind, p: Any, h: jax.Array,
                          positions: jax.Array, seats: jax.Array,
                          state: jax.Array, conv: jax.Array,
                          kernel: Optional[bool] = None):
    """One gated-delta-rule layer up to its output projection
    (``cfg.gated_delta``; ``ops/gated_delta.py`` has the mathematics): its
    input ``x``, the normed and gated ``o [B, T, H, dv]``, and the layer's
    pools with the rows' seats advanced.  Seats, fresh rows, pads and
    ``kernel`` as in :func:`linear_attention`; what differs is the rule:
    heads of ``dk`` x ``dv``, one log decay a head ``-exp(A_log) softplus(x
    Wa + dt_bias)``, a step size ``sigmoid(x Wb)`` (times 2 where
    ``linear_allow_neg_eigval``), the output's per-head norm times ``silu(x
    Wg)`` a channel, and a state pool that lays heads side by side
    (``gated_delta.to_pool``)."""
    from ..ops import delta_rule as dr
    from ..ops import gated_delta as gd

    B, T, _ = h.shape
    H, dk, dv = (kind.num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    valid = positions >= 0
    n = jnp.sum(valid, axis=1).astype(jnp.int32)
    fresh = positions[:, 0] == 0
    with jax.named_scope("gdn_proj"):
        x = (h if cfg.norm_placement == "post"
             else _rms_norm(h, p["attn_norm"], cfg.rms_norm_eps))
        q, k, v, a, b = jax.lax.optimization_barrier(
            (_mm(x, p["wq"]), _mm(x, p["gdn_wk"]), _mm(x, p["gdn_wv"]),
             _mm(x, p["gdn_wa"]), _mm(x, p["gdn_wb"])))
    with jax.named_scope("gdn_conv"):
        # a decode step where kernels run advances the seats' tails in the
        # pool itself (gd.conv_step_seats); a chunk gathers them (short_conv)
        y, conv = gd.conv_seats(
            conv, seats, fresh, n, (q, k, v), p,    # p: reads "gdn_conv"
            kernel=kernel)
        y = jax.nn.silu(y)
        q = dr.l2norm(y[..., :H * dk].reshape(B, T, H, dk)) * dk ** -0.5
        k = dr.l2norm(y[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
        v = y[..., 2 * H * dk:].reshape(B, T, H, dv)
        g = -jnp.exp(p["gdn_a_log"].astype(jnp.float32)) * jax.nn.softplus(
            a.astype(jnp.float32) + p["gdn_dt_bias"])
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        beta = jnp.where(valid[..., None], beta, 0.0)
    with jax.named_scope("gdn_recurrent" if T == 1 else "gdn_chunk"):
        if T == 1 and kernel is not None and state.dtype == jnp.float32:
            o, state = gd.gdn_step_seats(
                state, seats, fresh, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                beta[:, 0], interpret=kernel)
            o = o[:, None]
        else:
            s0 = jnp.where(
                fresh[:, None, None, None], 0,
                gd.from_pool(jnp.take(state, seats, axis=0), H)
            ).astype(jnp.float32)
            if T == 1:
                o, s1 = gd.gdn_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0])
                o = o[:, None]
            else:
                o, s1 = gd.gdn_chunked(s0, q, k, v, g, beta)
            state = state.at[seats].set(gd.to_pool(s1).astype(state.dtype))
    with jax.named_scope("gdn_out"):
        o = _rms_norm(o, p["gdn_o_norm"], cfg.rms_norm_eps)   # float32
        if cfg.linear_gate:
            gate = jax.nn.silu(_mm(x, p["gdn_wg"]).astype(jnp.float32))
            o = o * gate.reshape(B, T, H, dv)
        o = o.astype(h.dtype)
    return x, o, state, conv


def attn_inputs(cfg: ModelConfig, kind, p: Any, h: jax.Array,
                positions: jax.Array):
    """Normed input ``x`` (the raw stream where the model norms a
    sublayer's output instead) and the roped ``q [B, T, H, hd]``, ``k``,
    ``v [B, T, KV, hd]`` of one layer, ``H`` and the rope its kind's (none
    where its ``rope_type`` is "none"); ``q`` and ``k`` normed over the
    whole projection first where ``cfg.qk_norm``."""
    with jax.named_scope("qkv_proj"):
        x = (h if cfg.norm_placement == "post"
             else _rms_norm(h, p["attn_norm"], cfg.rms_norm_eps))
        q, k, v = _qkv_proj(x, p, kind.num_heads, cfg.num_kv_heads,
                            cfg.head_dim_)
        if cfg.qk_norm:      # over the whole projection, heads together
            B, T = h.shape[:2]
            q = _rms_norm(q.reshape(B, T, -1), p["q_norm"],
                          cfg.rms_norm_eps).reshape(q.shape)
            k = _rms_norm(k.reshape(B, T, -1), p["k_norm"],
                          cfg.rms_norm_eps).reshape(k.shape)
    with jax.named_scope("rope"):
        if cfg.has_table:
            rope = cfg.rope_of(kind)
            if rope.get("rope_type") != "none":
                q = _rope_kind(q, positions, rope)
                k = _rope_kind(k, positions, rope)
        else:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    return x, q, k, v


def attn_output(cfg: ModelConfig, p: Any, h: jax.Array, x: jax.Array,
                attn: jax.Array) -> jax.Array:
    """``h`` plus the output projection of ``attn [B, T, H, hd]`` (normed
    where ``cfg.norm_placement`` is "post"), each head first times its gate
    ``sigmoid(x Wg)`` where the model has one."""
    B, T, H, hd = attn.shape
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(_mm(x, p["w_attn_gate"]).astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * g[..., None]).astype(
                attn.dtype)
    with jax.named_scope("o_proj"):
        out = _mm(attn.reshape(B, T, H * hd), p["wo"])
        if cfg.norm_placement == "post":
            out = _rms_norm(out, p["attn_norm"], cfg.rms_norm_eps)
        return h + out


def ffn(cfg: ModelConfig, entry, p: Any, h: jax.Array, *,
        live: Optional[jax.Array] = None, interpret: bool = False,
        ff_pin=None, stats_out: Optional[list] = None,
        choices_out: Optional[list] = None,
        shortcut: Optional[list] = None) -> jax.Array:
    """``h`` plus the layer's FFN on its norm: the dense SwiGLU, the
    capacity experts of a model without a table (``cfg.is_moe``), or a
    table's sparse layer: the routed experts held here (``live [B, T]``
    rows only; their counters appended to ``stats_out``, every token's
    chosen experts ``[B, T, k]`` to ``choices_out``) plus the shared
    expert.  ``ff_pin`` pins the dense intermediates (the ring path).

    ``cfg.moe_shortcut``: a sparse row's routed sum (held experts and
    identities) is left in ``shortcut`` instead of joining ``h``, and the
    dense row that follows takes it from there and adds it behind its own
    FFN: the two rows are one double layer whose expert branch starts at the
    first FFN's input and ends after the second, so the second attention
    and FFN never see it.  The sparse row's shared expert is then the
    double layer's first dense FFN (scope ``mlp``)."""
    B, T, D = h.shape
    if cfg.moe_shortcut and shortcut is None:
        raise ValueError("a moe_shortcut table is run by forward, which "
                         "carries a sparse row's sum to the row behind it")
    if entry.ffn == "sparse":
        from ..parallel.moe import routed_ffn

        with jax.named_scope("moe_router"):
            x = _rms_norm(h, p["mlp_norm"], cfg.rms_norm_eps)
        # what a router beyond the softmax top-k is told (route's keywords)
        router = {}
        if (cfg.score_function != "softmax" or cfg.n_group
                or cfg.moe_router_enable_expert_bias):
            router = dict(score=cfg.score_function, n_group=cfg.n_group,
                          topk_group=cfg.topk_group,
                          bias=p.get("router_bias"))
        routed, stats, chosen = routed_ffn(
            x.reshape(B * T, D), p["w_router"], p["expert_gate"],
            p["expert_up"], p["expert_down"],
            top_k=cfg.num_experts_per_token,
            held_start=cfg.experts_held[0],
            renormalise=cfg.norm_topk_prob,
            scale=cfg.moe_routed_scaling_factor,
            live=None if live is None else live.reshape(B * T),
            n_zero=cfg.zero_expert_num, interpret=interpret, **router,
        )
        if stats_out is not None:
            stats_out.append(stats)
        if choices_out is not None:
            choices_out.append(chosen.reshape(B, T, -1))
        with jax.named_scope("mlp" if cfg.moe_shortcut else "moe_shared"):
            gate = jax.nn.silu(_mm(x, p["shared_gate"]).astype(jnp.float32))
            up = _mm(x, p["shared_up"]).astype(jnp.float32)
            shared = _mm((gate * up).astype(h.dtype), p["shared_down"])
            if cfg.moe_shortcut:
                shortcut.append(routed.reshape(B, T, D))
                return h + shared
            return h + routed.reshape(B, T, D) + shared
    post = cfg.norm_placement == "post"
    with jax.named_scope("mlp"):
        x = h if post else _rms_norm(h, p["mlp_norm"], cfg.rms_norm_eps)
        if cfg.is_moe:
            from ..parallel.moe import moe_ffn

            out = moe_ffn(
                x.reshape(B * T, D),
                p["w_router"],
                _dequant_leaf(p["w_gate"], x.dtype),
                _dequant_leaf(p["w_up"], x.dtype),
                _dequant_leaf(p["w_down"], x.dtype),
                top_k=cfg.num_experts_per_token,
                capacity_factor=cfg.moe_capacity_factor,
            )
            return h + out.reshape(B, T, D)
        gate = jax.nn.silu(_mm(x, p["w_gate"]).astype(jnp.float32))
        up = _mm(x, p["w_up"]).astype(jnp.float32)
        if ff_pin is not None:
            gate = jax.lax.with_sharding_constraint(gate, ff_pin)
            up = jax.lax.with_sharding_constraint(up, ff_pin)
        out = _mm((gate * up).astype(h.dtype), p["w_down"])
        if post:
            out = _rms_norm(out, p["mlp_norm"], cfg.rms_norm_eps)
        h = h + out
    if cfg.moe_shortcut and shortcut:
        with jax.named_scope("moe_shortcut"):
            h = h + shortcut.pop()
    return h


def forward(
    cfg: ModelConfig,
    eng: EngineConfig,
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B, T] int32 (0 = pad)
    positions: jax.Array,     # [B, T] int32 absolute, -1 = pad
    block_tables: jax.Array,  # [B, W] int32 physical block ids (0 = null)
    mesh: Optional[Mesh] = None,
    ring_mesh: Optional[Mesh] = None,
    mm_embeds: Optional[jax.Array] = None,  # [B, T, D] vision embeddings
    mm_mask: Optional[jax.Array] = None,    # [B, T] True = use mm_embeds
    moe_stats: Optional[list] = None,
    moe_choices: Optional[list] = None,
    seats: Optional[jax.Array] = None,      # [B] int32 seat of each row
) -> Tuple[Cache, jax.Array]:
    """Run the transformer over a token chunk, updating the paged cache.

    ``seats`` names each row's seat in the state pools of a table's
    linear-attention layers (``SchedSeq.slot``; the trash seat ``S`` for a
    pad row) and is required where the model has such layers
    (:func:`linear_attention`); nothing else reads it.

    ``moe_stats``, where a list is given, gains one int32 row a sparse
    layer of a table (``parallel.moe.MOE_STATS``), and ``moe_choices`` the
    layer's chosen experts ``[B, T, k]``: traced values of the caller's own
    trace.

    With ``ring_mesh`` set (an "sp" mesh over the same devices), the chunk
    MUST be a full fresh prompt (start position 0): its T axis is sharded
    over ``sp``, attention runs as an exact ppermute ring
    (parallel/ring_attention.py), and GSPMD reshards the chunk's K/V into
    the head-sharded paged cache — activations cost O(T / sp) per device.
    Pad tails are safe: ring causal masking is by absolute chunk index, so
    pad keys (index > every real query) never contaminate real rows.

    Returns (updated cache, hidden states [B, T, D]).
    """
    T = tokens.shape[1]
    bs = eng.block_size

    use_ring = ring_mesh is not None and T > 1
    if use_ring:
        refuse_table(cfg, what="the sequence-parallel ring prefill")
    if cfg.has_seat_state and seats is None:
        raise ValueError("a model with linear-attention layers is run with "
                         "the rows' seats: its state is a sequence's, not "
                         "a position's")
    ring_lay = SpecLayout.for_mesh(ring_mesh) if use_ring else None
    if use_ring and ring_lay.seq_axes() is None:
        use_ring = ring_lay = None  # single-device "ring" is dense attention
    # layer-boundary activation pin: ring chunks stay T-sharded over the
    # SERVING mesh's composite sequence axis, dense-path activations stay
    # replicated — one spec per boundary means GSPMD never has to guess
    # (and never falls back to involuntary rematerialization)
    lay = SpecLayout.for_mesh(mesh) if _multi(mesh) else None
    if use_ring:
        h_pin = NamedSharding(ring_mesh, ring_lay.hidden_seq())
    elif lay is not None:
        h_pin = NamedSharding(mesh, lay.hidden())
    else:
        h_pin = None

    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)  # [B, T, D]
        if mm_embeds is not None:
            # multimodal EPD: placeholder positions take the encode worker's
            # precomputed embeddings instead of token embeddings (ref: the
            # TRT-LLM EPD flow, request_handlers/handler_base.py:64-234 —
            # the reference splices prompt embeddings the same way)
            h = jnp.where(mm_mask[..., None], mm_embeds.astype(h.dtype), h)
        if h_pin is not None:
            h = jax.lax.with_sharding_constraint(h, h_pin)

    # THE FEED CONTRACT: a row's valid tokens (position >= 0) are a prefix
    # of the row, at contiguous positions start + t. The packed prefill
    # builds them as start + iota, a decode window has T == 1, spec verify
    # feeds pos0 + steps under a prefix mask, and the host's own feeds are
    # held to it by check_feed_positions. The write's page plan and the
    # ragged kernel's q_len / ctx_len below rest on it.
    with jax.named_scope("kv_write"):
        kv_pages = _kv_pages(positions, block_tables, bs)

    attn_class = attention_class(eng, T)
    use_pallas = (not use_ring
                  and resolve_attention_impl(eng, attn_class) == "pallas")
    if not use_pallas:  # the pallas paths note themselves, with their tiles
        _note_attention(attn_class, "ring" if use_ring else "einsum",
                        False, (0, 0))
    seq_lens = q_len = ctx_len = None
    if use_pallas:
        with jax.named_scope("attention"):
            if T == 1:
                seq_lens = jnp.maximum(positions[:, 0] + 1, 0)
            else:
                # valid tokens are a per-row prefix (the spec/prefill feed
                # contract), so count + max give the ragged-kernel metadata
                q_len = jnp.sum(positions >= 0, axis=1).astype(jnp.int32)
                ctx_len = jnp.maximum(jnp.max(positions, axis=1) + 1, 0)

    # Unrolled layer loop (NOT lax.scan): each layer's cache buffer is
    # donated and scatter-updated in place; a scanned stacked cache is
    # copied out of xs and back into ys wholesale every step (profiled at
    # ~90 ms/step of pure copies for a 1B model on v5e). Weights stay
    # stacked [L, …]; the static per-layer slice is fused into the matmul
    # that reads it (for wq / wk because _qkv_proj keeps them matmuls).
    kv_quant = quant.is_quantized(eng.kv_dtype)
    new_k: list = []
    new_v: list = []
    new_ks: list = []
    new_vs: list = []
    new_latent: list = []
    new_state: list = []
    new_conv: list = []
    stacked = params["layers"]
    interpret = pallas_interpret(mesh) if cfg.has_routed_experts else False
    live = positions >= 0 if cfg.has_routed_experts else None
    ff_pin = (NamedSharding(ring_mesh,
                            layout.spec(None, ring_lay.seq_axes(), None))
              if use_ring else None)
    shortcut: list = []      # a double layer's pending routed sum (ffn)
    for li in range(cfg.num_layers):
        p, entry, kind = layer_params(cfg, stacked, li)
        if kind.name in (STATE_KIND, LATENT_KIND):
            at = entry.attn_at
            if kind.name == STATE_KIND:
                mixer = (gated_delta_attention if cfg.gated_delta
                         else linear_attention)
                x, attn, state, conv = mixer(
                    cfg, kind, p, h, positions, seats,
                    cache["state"][at], cache["conv"][at],
                    kernel=pallas_interpret(mesh) if use_pallas else None)
                new_state.append(state)
                new_conv.append(conv)
            else:
                x, q_nope, q_pe, latent = latent_inputs(
                    cfg, kind, p, h, positions)
                with jax.named_scope("kv_write"):
                    plane = _kv_write(cache["latent"][at], *kv_pages, latent,
                                      mesh)
                with jax.named_scope("attention_latent"):
                    if use_pallas and T == 1:
                        attn = _paged_latent_decode(
                            cfg, eng, mesh, p, q_nope, q_pe, plane,
                            block_tables, seq_lens)
                    else:
                        B, W = block_tables.shape
                        ctx = jnp.take(
                            plane, block_tables.reshape(-1), axis=0
                        ).reshape(B, W * bs, plane.shape[-1])
                        tiles = latent_chunk_tiles(mesh, T, W * bs)
                        if tiles:
                            attn = _latent_chunk(cfg, mesh, p, q_nope, q_pe,
                                                 ctx, positions, tiles)
                        else:
                            attn = latent_attention(
                                cfg, p, q_nope, q_pe, ctx, positions,
                                absorbed=T == 1)
                new_latent.append(plane)
            h = attn_output(cfg, p, h, x, attn)
            h = ffn(cfg, entry, p, h, live=live, interpret=interpret,
                    stats_out=moe_stats, choices_out=moe_choices,
                    shortcut=shortcut)
            continue
        kv_at = kv_layer_index(cfg, li)
        lk, lv = cache["k"][kv_at], cache["v"][kv_at]   # [NB, KV, bs, hd]
        lks = cache["ks"][kv_at] if kv_quant else None  # [NB, KV, bs] f32
        lvs = cache["vs"][kv_at] if kv_quant else None

        x, q, k, v = attn_inputs(cfg, kind, p, h, positions)
        if use_ring:
            # projections of the T-sharded chunk stay T-sharded — without
            # the pin the column-sharded wq/wk/wv propagate a head
            # sharding into the same tensors and GSPMD remats
            qkv_pin = NamedSharding(ring_mesh, ring_lay.heads_seq())
            q = jax.lax.with_sharding_constraint(q, qkv_pin)
            k = jax.lax.with_sharding_constraint(k, qkv_pin)
            v = jax.lax.with_sharding_constraint(v, qkv_pin)

        # write this chunk's K/V into the paged cache, page by page
        with jax.named_scope("kv_write"):
            k_upd, v_upd = k, v                     # [B, T, KV, hd]
            if use_ring and lay is not None:
                # the one real layout change on the ring path: T-sharded
                # K/V re-lands on the cache's head sharding. GSPMD cannot
                # synthesize the seq->heads transform in one hop (it falls
                # back to involuntary full rematerialization), so stage it
                # explicitly: a planned all-gather over the sequence axes,
                # then a local slice onto the cache's tp sharding
                repl_pin = NamedSharding(
                    mesh, layout.spec(None, None, None, None))
                upd_pin = NamedSharding(
                    mesh, layout.spec(None, None, lay.tp, None))
                k_upd = jax.lax.with_sharding_constraint(k_upd, repl_pin)
                v_upd = jax.lax.with_sharding_constraint(v_upd, repl_pin)
                k_upd = jax.lax.with_sharding_constraint(k_upd, upd_pin)
                v_upd = jax.lax.with_sharding_constraint(v_upd, upd_pin)
            if kv_quant:
                # per-(token, head) scales: a token's stored bytes depend
                # only on its own K/V, never on block placement — so
                # spec-decode and chunked-prefill replays of the same
                # tokens stay bit-exact regardless of which block a replay
                # scatters to
                k_upd, k_sc = quant.kv_quantize(k_upd, eng.kv_dtype)
                v_upd, v_sc = quant.kv_quantize(v_upd, eng.kv_dtype)
                lks = _kv_write(lks, *kv_pages, k_sc, mesh)
                lvs = _kv_write(lvs, *kv_pages, v_sc, mesh)
            lk = _kv_write(lk, *kv_pages, k_upd, mesh)
            lv = _kv_write(lv, *kv_pages, v_upd, mesh)

        # the trace tells a window layer's walk from a full one's
        with jax.named_scope(
                "attention_window" if kind.window else "attention"):
            attn = _layer_attention(
                eng, mesh, ring_mesh, ring_lay, use_pallas, q, k, v,
                lk, lv, lks, lvs, positions, block_tables,
                seq_lens, q_len, ctx_len, kind.window,
            )
        h = attn_output(cfg, p, h, x, attn)
        # ring chunks run the MLP sequence-parallel: activations stay
        # T-sharded, the (small) weights all-gather — ff_pin pins the
        # intermediates so w_down's row sharding can't pull a head-style
        # spec onto them
        h = ffn(cfg, entry, p, h, live=live, interpret=interpret,
                ff_pin=ff_pin, stats_out=moe_stats, choices_out=moe_choices,
                shortcut=shortcut)
        if h_pin is not None:
            with jax.named_scope("mlp"):
                h = jax.lax.with_sharding_constraint(h, h_pin)
        new_k.append(lk)
        new_v.append(lv)
        if kv_quant:
            new_ks.append(lks)
            new_vs.append(lvs)

    with jax.named_scope("final_norm"):
        h = _rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    out_cache: Cache = {
        key: rows for key, rows in (
            ("k", new_k), ("v", new_v), ("ks", new_ks), ("vs", new_vs),
            ("latent", new_latent), ("state", new_state), ("conv", new_conv))
        if rows}
    return out_cache, h


def logits_fn(cfg: ModelConfig, params: Params, h: jax.Array) -> jax.Array:
    head = (params["embed"].T if cfg.tie_word_embeddings
            else params["lm_head"])
    # bf16 x bf16 -> f32 on the MXU; casting the [D, V] head to f32 first
    # would materialise ~1 GB in HBM every step
    with jax.named_scope("lm_head"):
        if isinstance(head, dict):
            y = jax.lax.dot_general(
                h, head["q"].astype(h.dtype),
                (((h.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return y * head["s"][0]
        return jax.lax.dot_general(
            h, head.astype(h.dtype), (((h.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


# --------------------------- encode (embeddings) --------------------------


def encode_forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,      # [B, T] int32 (0 = pad)
    positions: jax.Array,   # [B, T] int32, -1 = pad
) -> jax.Array:
    """Encode-only forward: dense causal attention over the chunk, no paged
    cache — the engine step for ``/v1/embeddings`` (ref: the embeddings
    route in lib/llm/src/http/service/openai.rs:714; the reference delegates
    to an embedding engine, here the decoder itself encodes).

    Returns L2-normalised mean-pooled final hidden states ``[B, D]`` (mean
    over non-pad positions — the standard decoder-as-encoder pooling).
    """
    h = jnp.take(params["embed"], tokens, axis=0)  # [B, T, D]
    stacked = params["layers"]
    interpret = pallas_interpret(None) if cfg.has_routed_experts else False
    live = positions >= 0 if cfg.has_routed_experts else None
    for li in range(cfg.num_layers):
        p, entry, kind = layer_params(cfg, stacked, li)
        x, q, k, v = attn_inputs(cfg, kind, p, h, positions)
        attn = _attention(q, k, v, positions, kind.window)
        h = attn_output(cfg, p, h, x, attn)
        h = ffn(cfg, entry, p, h, live=live, interpret=interpret)
    h = _rms_norm(h, params["final_norm"], cfg.rms_norm_eps)

    valid = (positions >= 0).astype(jnp.float32)[:, :, None]  # [B, T, 1]
    pooled = jnp.sum(h.astype(jnp.float32) * valid, axis=1) / jnp.maximum(
        jnp.sum(valid, axis=1), 1.0
    )                                                          # [B, D]
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-12)


def make_encode_fn(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                   weight_dtype: str = "bf16"):
    """Jitted encode step: (params, tokens[B,T], positions[B,T]) -> [B, D].

    ``mesh`` pins params to the canonical layout (pooled embeddings are
    tiny and come back replicated)."""
    refuse_unpaged(cfg, "the encoder (/v1/embeddings attends a chunk's own "
                        "K and V)")
    kw: Dict[str, Any] = {}
    if _multi(mesh):
        lay = SpecLayout.for_mesh(mesh)
        repl = layout.replicated(mesh)
        kw["in_shardings"] = (
            lay.param_shardings(mesh, cfg, weight_dtype), repl, repl
        )
        kw["out_shardings"] = repl
    return compilewatch.label(
        jax.jit(functools.partial(encode_forward, cfg), **kw), "encode"
    )


# ----------------------------- sampling ----------------------------------


MAX_TOP_K = 64  # top-k above this is clamped; the top-p nucleus is found
                # among these candidates (a >64-token nucleus clamps to 64)


def _row_keys(
    rng: jax.Array, seeds: jax.Array, positions: jax.Array
) -> jax.Array:
    """Per-row PRNG keys. Seeded rows (seed >= 0) get
    ``fold_in(PRNGKey(seed), position)`` — deterministic across runs,
    engine restarts, and batch composition. Unseeded rows (-1) derive from
    the engine's step rng, decorrelated per row."""

    def mk(seed, pos, i):
        seeded = jax.random.fold_in(
            jax.random.PRNGKey(jnp.maximum(seed, 0)), jnp.maximum(pos, 0)
        )
        anon = jax.random.fold_in(rng, i)
        return jnp.where(seed >= 0, seeded, anon)

    return jax.vmap(mk)(seeds, positions, jnp.arange(seeds.shape[0]))


@jax.named_scope("sample")
def sample(
    logits: jax.Array,      # [B, V] float32
    rng: jax.Array,
    temperature: jax.Array,  # [B] 0.0 = greedy
    top_k: jax.Array,        # [B] 0 = disabled
    top_p: jax.Array,        # [B] <=0 or >=1 = disabled
    seeds: jax.Array,        # [B] per-request seed, -1 = engine rng
    positions: jax.Array,    # [B] absolute position being sampled
) -> jax.Array:
    """Greedy / temperature / top-k / top-p sampling, vectorised over the
    batch (ref sampling surface: lib/llm/src/protocols/common SamplingOptions
    — temperature, top_k, top_p, seed).

    The stochastic path runs under ``lax.cond`` so an all-greedy batch — the
    common serving case — pays only the argmax. Thresholds come from
    ``lax.top_k`` over MAX_TOP_K candidates, never a full V-sort; the top-p
    nucleus is therefore capped at MAX_TOP_K tokens (documented clamp, same
    spirit as the top-k cap). Seeded rows draw from their own key stream so
    (seed → output tokens) is reproducible regardless of what else is in the
    batch; sampling is gumbel-max with per-row keys.
    """
    greedy = jnp.argmax(logits, axis=-1)

    def stochastic(_):
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = logits / temp                               # [B, V]
        K = min(MAX_TOP_K, logits.shape[-1])
        k_vals, _ = jax.lax.top_k(scaled, K)                 # [B, K] desc
        # top-k threshold: the kth largest value (k clamped to K)
        safe_k = jnp.clip(top_k, 1, K)
        kth = jnp.take_along_axis(k_vals, (safe_k - 1)[:, None], axis=-1)
        thresh = jnp.where(top_k[:, None] > 0, kth, -jnp.inf)  # [B, 1]
        # top-p threshold: smallest candidate still inside the nucleus
        # (probabilities under the full softmax, candidates in desc order;
        # the first candidate is always kept)
        lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
        probs_k = jnp.exp(k_vals - lse)                      # [B, K]
        cum = jnp.cumsum(probs_k, axis=-1)
        p_on = (top_p > 0.0) & (top_p < 1.0)                 # [B]
        keep = (cum - probs_k) < jnp.where(p_on, top_p, 2.0)[:, None]
        pth = jnp.min(
            jnp.where(keep, k_vals, jnp.inf), axis=-1, keepdims=True
        )
        thresh = jnp.maximum(
            thresh, jnp.where(p_on[:, None], pth, -jnp.inf)
        )
        masked = jnp.where(scaled >= thresh, scaled, -jnp.inf)
        # gumbel-max with per-row keys (categorical would share one key
        # across the batch, breaking per-request determinism)
        keys = _row_keys(rng, seeds, positions)
        u = jax.vmap(
            lambda k: jax.random.uniform(
                k, (logits.shape[-1],),
                minval=jnp.finfo(jnp.float32).tiny, maxval=1.0,
            )
        )(keys)
        sampled = jnp.argmax(masked - jnp.log(-jnp.log(u)), axis=-1)
        return jnp.where(temperature > 0.0, sampled, greedy)

    out = jax.lax.cond(
        jnp.any(temperature > 0.0), stochastic, lambda _: greedy, None
    )
    return out.astype(jnp.int32)


# --------------------------- the step function ----------------------------


def _last_logits(cfg: ModelConfig, params: Params, h: jax.Array,
                 positions: jax.Array, last_idx: jax.Array):
    """Logits [B, V] and absolute position [B] of each row's last valid
    chunk token (``last_idx``) — the one a prefill step samples from."""
    with jax.named_scope("lm_head"):
        h_last = h[jnp.arange(h.shape[0]), last_idx]     # [B, D]
    logits = logits_fn(cfg, params, h_last)              # [B, V]
    with jax.named_scope("sample"):
        pos_last = jnp.take_along_axis(
            positions, last_idx[:, None], axis=1
        )[:, 0]
    return logits, pos_last


def raw_step_fn(cfg: ModelConfig, eng: EngineConfig,
                mesh: Optional[Mesh] = None,
                ring_mesh: Optional[Mesh] = None):
    """The unjitted unified prefill/decode step.

    Signature:
      step(params, cache, tokens[B,T], positions[B,T], block_tables[B,W],
           last_idx[B], rng, temperature[B], top_k[B], top_p[B], seeds[B],
           seats[B] = None)
        -> (cache, sampled[B])

    ``last_idx[b]`` selects which chunk position's logits to sample (the last
    valid token of the chunk).
    """

    def step(params, cache, tokens, positions, block_tables,
             last_idx, rng, temperature, top_k, top_p, seeds, seats=None):
        cache, h = forward(
            cfg, eng, params, cache, tokens, positions, block_tables,
            mesh=mesh, ring_mesh=ring_mesh, seats=seats,
        )
        logits, pos_last = _last_logits(cfg, params, h, positions, last_idx)
        sampled = sample(
            logits, rng, temperature, top_k, top_p, seeds, pos_last
        )
        return cache, sampled

    return step


def make_step_fn(cfg: ModelConfig, eng: EngineConfig, mesh: Optional[Mesh]):
    """Jitted step with the cache donated — XLA updates it in place.

    On a multi-device mesh both sides of the jit boundary are pinned to the
    canonical ``SpecLayout``: params/cache in their table layout, data args
    replicated, the updated cache back out in the cache layout."""
    return compilewatch.label(
        jax.jit(
            raw_step_fn(cfg, eng, mesh), donate_argnums=(1,),
            **_io_kwargs(mesh, cfg, 9, ("cache", "repl"), eng=eng),
        ),
        "step",
    )


# ---------------- device-resident token ring (pipelined serving) ----------
#
# The serving hot loop must never wait on the host. The design's premise:
# ONE host sync ~64 ms — 20× the 1B model's 3 ms decode step — against
# ~0.3 ms for an enqueue-only dispatch (measured on an earlier transport;
# re-measured by chip_smoke.py, see CHANGES).
# The fix is architectural, not a kernel: keep the autoregressive token
# feed ON DEVICE. ``last_tok`` is a small [S+1] int32 buffer indexed by a
# per-sequence slot id; every prefill/decode step writes the token it
# sampled into the sequence's slot, and decode windows READ their input
# token from it. The host then only *observes* sampled tokens (fetched
# asynchronously, one-plus windows behind) for detokenisation and stop
# checks — it is never in the dispatch critical path. Slot S is a trash
# slot (rows with slot -1 write there).
#
# Ref for the serving shape this replaces: the reference engine's
# per-step host loop (components/backends/vllm — vLLM's GPU worker reads
# sampled ids back every step; on GPU a sync is ~10 µs so it can afford
# to). TPU-first redesign: same tokens, no sync.


# ------------------- decode autopilot (device-resident control) -----------
#
# The token ring removed the host from the token FEED; the autopilot
# removes it from the control feed too. Premise: each host→device array
# upload costs ~15 ms of serial channel time, so a decode window that
# uploads 11 small arrays spends 160 ms on the channel for 3 ms of
# compute (1B model; measured on an earlier transport; re-measured by
# chip_smoke.py, see CHANGES). So ALL per-sequence decode state lives on
# device, indexed by slot:
#
#   ctl = {pos, valid_until, temp, top_k, top_p, seed, last_tok [S+1],
#          tables [S+1, Wcap], rng key, ctr}
#
# A steady-state decode window is dispatched with NO fresh host arrays —
# the executable reads its seats from a device-resident ``slot_rows``
# map. The host pushes packed DELTAS (one int32 [n, 6+Wcap] + one f32
# [n, 2] upload) only when membership joins/leaves, blocks grow, or a
# resumed sequence injects a host-known token, and re-uploads
# ``slot_rows`` only on membership changes. Slot S is the trash slot:
# delta pad rows target it, and dead seats (valid_until 0) advance
# nothing and write nothing to the cache.
#
# This is the TPU-first redesign of the reference's per-step engine loop
# (vLLM reads sampled ids back every step — affordable at ~10 µs GPU
# sync, fatal at tens of ms): the device runs the decode loop; the host is a
# delta stream plus a lagging observer.

CTL_I32_FIELDS = 6  # slot, pos, valid_until, top_k, seed, last_tok


def init_ctl(eng: EngineConfig, S: int, Wcap: int, seed: int = 0,
             hist_cap: int = 0):
    """Fresh device control state (host-side construction; device_put by
    the caller with a replicated sharding).

    ``hist_cap > 0`` (spec decode) adds a per-seat token history ``hist``
    [S+1, hist_cap+1] for the n-gram drafter: ``hist[s, p]`` is sequence
    s's token at position p, -1 = unknown; column hist_cap is a trash
    column for padded scatters. The autopilot window/delta fns pass the
    extra key through untouched."""
    ctl = {
        "pos": np.zeros((S + 1,), np.int32),
        "vu": np.zeros((S + 1,), np.int32),
        "temp": np.zeros((S + 1,), np.float32),
        "tk": np.zeros((S + 1,), np.int32),
        "tp": np.ones((S + 1,), np.float32),
        "seed": np.full((S + 1,), -1, np.int32),
        "last_tok": np.zeros((S + 1,), np.int32),
        "tables": np.zeros((S + 1, Wcap), np.int32),
        "key": jax.random.PRNGKey(seed),
        "ctr": np.zeros((), np.int32),
    }
    if hist_cap > 0:
        ctl["hist"] = np.full((S + 1, hist_cap + 1), -1, np.int32)
    return ctl


def raw_ctl_delta_fn(Wcap: int):
    """Apply a packed delta to the control state.

    delta_i32 [n, 6 + Wcap]: slot, pos, valid_until, top_k, seed,
    last_tok (-1 = keep the ring value — joins after an on-device prefill
    must not clobber the sampled token), then the full table row.
    delta_f32 [n, 2]: temperature, top_p. Pad rows use slot = S (trash).
    """

    @jax.named_scope("ctl")
    def apply(ctl, delta_i32, delta_f32):
        slots = delta_i32[:, 0]
        ctl = dict(ctl)
        ctl["pos"] = ctl["pos"].at[slots].set(delta_i32[:, 1])
        ctl["vu"] = ctl["vu"].at[slots].set(delta_i32[:, 2])
        ctl["tk"] = ctl["tk"].at[slots].set(delta_i32[:, 3])
        ctl["seed"] = ctl["seed"].at[slots].set(delta_i32[:, 4])
        lt = delta_i32[:, 5]
        ctl["last_tok"] = ctl["last_tok"].at[slots].set(
            jnp.where(lt >= 0, lt, ctl["last_tok"][slots])
        )
        ctl["tables"] = ctl["tables"].at[slots].set(delta_i32[:, 6:])
        ctl["temp"] = ctl["temp"].at[slots].set(delta_f32[:, 0])
        ctl["tp"] = ctl["tp"].at[slots].set(delta_f32[:, 1])
        return ctl

    return apply


def raw_autopilot_window_fn(cfg: ModelConfig, eng: EngineConfig,
                            mesh: Optional[Mesh] = None):
    """One decode step reading EVERYTHING from device state.

    Signature: window(params, cache, ctl, slot_rows[B]) ->
    (cache, ctl, samples[1, B]).

    Dead seats (valid_until <= pos) compute garbage, write none of it to
    the cache or the ring and advance nothing; their sample columns are
    discarded by the host.
    The step's rng derives from the carried key + counter, so a window
    dispatch carries zero fresh host arrays.

    A table with routed experts adds one row to ``samples`` (``[2, B]``,
    :func:`moe_stats_row`): the window's routing counters ride the one
    fetch the sampled tokens already make.
    """

    def window(params, cache, ctl, slot_rows):
        moe_stats = [] if cfg.has_routed_experts else None
        rows = slot_rows
        with jax.named_scope("ctl"):
            tok = ctl["last_tok"][rows][:, None]
            pos0 = ctl["pos"][rows]
            vu = ctl["vu"][rows]
            temp = ctl["temp"][rows]
            tk = ctl["tk"][rows]
            tp = ctl["tp"][rows]
            sd = ctl["seed"][rows]
            tables = ctl["tables"][rows]
            pos = pos0[:, None]
            rng = jax.random.fold_in(ctl["key"], ctl["ctr"])
            pos_eff = jnp.where(pos < vu[:, None], pos, -1)
        cache, h = forward(
            cfg, eng, params, cache, tok, pos_eff, tables, mesh=mesh,
            moe_stats=moe_stats, seats=rows,
        )
        with jax.named_scope("lm_head"):
            h_last = h[:, 0]
        logits = logits_fn(cfg, params, h_last)
        s = sample(logits, rng, temp, tk, tp, sd, pos[:, 0])
        ctl = dict(ctl)
        with jax.named_scope("ctl"):
            samples = s[None, :]                               # [1, B]
            acc = jnp.clip(vu - pos0, 0, 1)                    # [B]
            # row 0 of one.  Written as the take it was under a loop of one,
            # so that the windows compile to the programs they were, hash
            # for hash (CHANGES.md, PR 51)
            final = jnp.take_along_axis(
                samples, jnp.maximum(acc - 1, 0)[None, :], axis=0
            )[0]
            S = ctl["last_tok"].shape[0] - 1
            write_rows = jnp.where(acc > 0, rows, S)
            ctl["last_tok"] = ctl["last_tok"].at[write_rows].set(final)
            # duplicate trash rows accumulate zero (acc there is 0)
            ctl["pos"] = ctl["pos"].at[rows].add(acc)
            ctl["ctr"] = ctl["ctr"] + 1
            if moe_stats:
                samples = jnp.concatenate(
                    [samples, moe_stats_row(moe_stats, samples.shape[1])])
        return cache, ctl, samples

    return window


def moe_stats_row(stats: list, width: int) -> jax.Array:
    """The routing counters of a window's sparse layers and steps (each
    an int32 row of ``parallel.moe.MOE_STATS``, or of all but its last
    where a call's rows are all its pairs) as one ``[1, width]`` int32 row:
    sums of pairs, pairs held, experts touched and identity pairs, the
    largest load, the sum of the held pairs behind a call's first slab;
    zeros behind them."""
    from ..parallel.moe import MOE_STATS

    if width < len(MOE_STATS):
        raise ValueError(f"a decode bucket of {width} rows cannot carry "
                         f"the {len(MOE_STATS)} routing counters")
    per = jnp.stack(stats)               # [n, len(MOE_STATS) or one less]
    at = MOE_STATS.index("moe_load_max")
    row = [jnp.sum(per[:, :at], axis=0), jnp.max(per[:, at:at + 1], axis=0)]
    if per.shape[1] > at + 1:
        row.append(jnp.sum(per[:, at + 1:], axis=0))
    row = jnp.concatenate(row)
    return jnp.pad(row, (0, width - row.shape[0]))[None, :]


def make_autopilot_fns(cfg: ModelConfig, eng: EngineConfig, Wcap: int,
                       mesh: Optional[Mesh] = None):
    """(window_fn, delta_fn) jitted with cache/ctl donated."""
    window = compilewatch.label(
        jax.jit(
            raw_autopilot_window_fn(cfg, eng, mesh), donate_argnums=(1, 2),
            **_io_kwargs(mesh, cfg, 2, ("cache", "repl", "repl"), eng=eng),
        ),
        "decode_window",
    )
    delta = compilewatch.label(
        jax.jit(
            raw_ctl_delta_fn(Wcap), donate_argnums=(0,),
            **_repl_kwargs(mesh, 3),
        ),
        "ctl_delta",
    )
    return window, delta


# ------------------- speculative decode window (draft + verify) -----------
#
# One autopilot window lands one token per host sync. The spec
# window raises the per-sync yield without a draft model: an on-device
# prompt-lookup drafter (spec/ngram.py) proposes up to k continuation
# tokens from the seat's own token history, and ONE [B, k+1] ragged
# forward verifies the chain against the paged cache — accepted prefix +
# one bonus/corrective token land per sync, up to k+1 total. Greedy rows
# are exactly parity-safe: every emitted token is the target model's own
# argmax given a correct prefix. Draft tokens that get rejected DO write
# KV at positions past the accepted point, but those positions are (a)
# never attendable by any accepted query (the causal mask is
# ``kpos <= q``), and (b) always re-scattered by the next window before
# any later query reads them — so rejected tokens never poison the cache.


def raw_spec_window_fn(cfg: ModelConfig, eng: EngineConfig, k: int,
                       ngram_min: int, ngram_max: int,
                       mesh: Optional[Mesh] = None):
    """Draft + batched-verify decode window.

    Signature: window(params, cache, ctl, slot_rows[B]) ->
    (cache, ctl, packed[k+3, B]) where packed rows 0..k are the emitted
    token candidates, row k+1 is n_emitted per seat (how many of them are
    real), and row k+2 is n_drafted (accounting).

    Drafting is restricted to greedy seats (temp <= 0); sampled seats run
    the window as a plain single-token decode step, keyed by position for
    seeded rows exactly like the non-spec path. Dead seats (vu <= pos)
    feed trash and emit 0 tokens.
    """
    from ..spec.ngram import propose_drafts

    def window(params, cache, ctl, slot_rows):
        rows = slot_rows                                   # [B]
        with jax.named_scope("ctl"):
            tok0 = ctl["last_tok"][rows]
            pos0 = ctl["pos"][rows]
            vu = ctl["vu"][rows]
            temp = ctl["temp"][rows]
            tk = ctl["tk"][rows]
            tp = ctl["tp"][rows]
            sd = ctl["seed"][rows]
            tables = ctl["tables"][rows]
            hist = ctl["hist"]                                 # [S+1, Hcap+1]
            S = ctl["last_tok"].shape[0] - 1
            Hcap = hist.shape[1] - 1
            live = vu > pos0
            # keep the history coherent with the ring: the window's input token
            # IS all_tokens[pos0] (defensive — joins already host-fill it)
            hist = hist.at[
                jnp.where(live, rows, S),
                jnp.where(live, jnp.clip(pos0, 0, Hcap - 1), Hcap),
            ].set(tok0)
            drafts = propose_drafts(hist[rows], pos0, k, ngram_min, ngram_max)
            drafts = jnp.where((temp <= 0.0)[:, None], drafts, -1)  # [B, k]
            dvalid = jnp.cumprod(
                (drafts >= 0).astype(jnp.int32), axis=1
            ).astype(bool)
            steps = jnp.arange(k + 1, dtype=jnp.int32)
            toks = jnp.concatenate(
                [tok0[:, None], jnp.where(dvalid, drafts, 0)], axis=1
            )                                                  # [B, k+1]
            pos = pos0[:, None] + steps[None, :]
            feed = jnp.concatenate(
                [jnp.ones_like(dvalid[:, :1]), dvalid], axis=1
            ) & (pos < vu[:, None])
            pos_eff = jnp.where(feed, pos, -1)
        cache, h = forward(
            cfg, eng, params, cache, toks, pos_eff, tables, mesh=mesh,
        )
        logits = logits_fn(cfg, params, h)                 # [B, k+1, V]
        with jax.named_scope("sample"):
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k+1]
        rng_w = jax.random.fold_in(ctl["key"], ctl["ctr"])
        s0 = sample(logits[:, 0], rng_w, temp, tk, tp, sd, pos0)
        ctl = dict(ctl)
        with jax.named_scope("ctl"):
            emitted = jnp.concatenate([s0[:, None], g[:, 1:]], axis=1)
            # accept the longest draft prefix the target model reproduces; the
            # query at index i (position pos0+i) verifies draft i
            match = dvalid & (drafts == g[:, :k])              # [B, k]
            a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            cap = jnp.clip(vu - pos0, 0, k + 1)
            n = jnp.minimum(a + 1, cap)                        # [B] emitted
            final = jnp.take_along_axis(
                emitted, jnp.maximum(n - 1, 0)[:, None], axis=1
            )[:, 0]
            write_rows = jnp.where(n > 0, rows, S)
            ctl["last_tok"] = ctl["last_tok"].at[write_rows].set(final)
            # duplicate trash rows accumulate zero (n there is 0)
            ctl["pos"] = ctl["pos"].at[rows].add(n)
            # append the landed tokens to the history (emitted j is
            # all_tokens[pos0+1+j]); rejects route to the trash cell
            hv = steps[None, :] < n[:, None]
            ctl["hist"] = hist.at[
                jnp.where(hv, rows[:, None], S),
                jnp.where(hv, jnp.clip(pos0[:, None] + 1 + steps[None, :],
                                       0, Hcap - 1), Hcap),
            ].set(emitted)
            ctl["ctr"] = ctl["ctr"] + 1
            ndraft = jnp.sum(dvalid.astype(jnp.int32), axis=1)
            packed = jnp.concatenate(
                [emitted.T, n[None, :], ndraft[None, :]], axis=0
            ).astype(jnp.int32)                                # [k+3, B]
        return cache, ctl, packed

    return window


def raw_spec_hist_fill_fn():
    """Host-side history injection for joining/resumed seats.

    fill(ctl, slots[n], rows[n, Hcap+1]) scatters full token-history rows
    (-1-padded) into ``ctl["hist"]``. Pad entries use slot = S (trash).
    Dispatched only on seat joins/resets — steady-state spec windows
    maintain the history on device with zero host uploads.
    """

    @jax.named_scope("ctl")
    def fill(ctl, slots, rows):
        ctl = dict(ctl)
        ctl["hist"] = ctl["hist"].at[slots].set(rows)
        return ctl

    return fill


def make_spec_fns(cfg: ModelConfig, eng: EngineConfig, k: int,
                  ngram_min: int, ngram_max: int,
                  mesh: Optional[Mesh] = None):
    """(spec_window_fn, hist_fill_fn) jitted with cache/ctl donated."""
    refuse_unpaged(cfg, "speculative decoding (a rejected draft rolls back)")
    window = compilewatch.label(
        jax.jit(
            raw_spec_window_fn(cfg, eng, k, ngram_min, ngram_max, mesh),
            donate_argnums=(1, 2),
            **_io_kwargs(mesh, cfg, 2, ("cache", "repl", "repl"), eng=eng),
        ),
        "spec_window",
    )
    fill = compilewatch.label(
        jax.jit(
            raw_spec_hist_fill_fn(), donate_argnums=(0,),
            **_repl_kwargs(mesh, 3),
        ),
        "spec_hist_fill",
    )
    return window, fill


def raw_ring_prefill_fn(cfg: ModelConfig, eng: EngineConfig,
                        mesh: Optional[Mesh] = None,
                        ring_mesh: Optional[Mesh] = None):
    """Unified prefill step that also posts its sampled token to the ring.

    Same compute as ``raw_step_fn`` plus:
      write_mask[B] (int32): rows completing their prompt write ``sampled``
      into ``last_tok[slot]`` so the first decode window chains on device.
    Non-completing chunks pass write_mask 0 (their sampled is discarded).

    Signature:
      prefill(params, cache, last_tok, tokens[B,T], positions[B,T],
              block_tables[B,W], last_idx[B], slot_ids[B], write_mask[B],
              rng, temperature[B], top_k[B], top_p[B], seeds[B])
        -> (cache, last_tok, sampled[B])
    """
    base = raw_step_fn(cfg, eng, mesh, ring_mesh=ring_mesh)

    def prefill(params, cache, last_tok, tokens, positions, block_tables,
                last_idx, slot_ids, write_mask, rng,
                temperature, top_k, top_p, seeds):
        cache, sampled = base(
            params, cache, tokens, positions, block_tables, last_idx,
            rng, temperature, top_k, top_p, seeds, seats=slot_ids,
        )
        with jax.named_scope("ctl"):
            S = last_tok.shape[0] - 1  # trash slot
            slot_eff = jnp.where(write_mask > 0, slot_ids, S)
            last_tok = last_tok.at[slot_eff].set(sampled)
        return cache, last_tok, sampled

    return prefill


PP_SCALARS = 8   # n, start, slot, write, top_k, seed, temp_q, top_p_q
PP_QUANT = 1e4   # temperature / top_p fixed-point scale in the int pack


def raw_packed_prefill_fn(cfg: ModelConfig, eng: EngineConfig,
                          T: int, W: int,
                          mesh: Optional[Mesh] = None):
    """Ring prefill with ALL inputs packed into ONE upload.

    ``pint [1, T + W + PP_SCALARS]`` = tokens(T), tables(W), then n,
    start, slot, write, top_k, seed, temp*1e4, top_p*1e4 (fixed-point —
    1e-4 sampling-parameter resolution is far below any behavioral
    threshold). Positions are derived on device (start + iota, -1 pads),
    so one prefill costs ONE host upload instead of 8 — at ~15 ms of
    serial channel time per upload the prefill upload stream was the
    single largest channel consumer at ISL 512 (measured on an earlier
    transport; re-measured by chip_smoke.py, see CHANGES).
    """
    base = raw_step_fn(cfg, eng, mesh)

    def prefill(params, cache, last_tok, pint, rng):
        with jax.named_scope("ctl"):
            tokens = pint[:, :T]
            tables = pint[:, T:T + W]
            n = pint[0, T + W + 0]
            start = pint[0, T + W + 1]
            slot = pint[0, T + W + 2]
            write = pint[0, T + W + 3]
            top_k = pint[0:1, T + W + 4]
            seed = pint[0:1, T + W + 5]
            temp = pint[0:1, T + W + 6].astype(jnp.float32) / PP_QUANT
            tp = pint[0:1, T + W + 7].astype(jnp.float32) / PP_QUANT
            idx = jnp.arange(T, dtype=jnp.int32)
            positions = jnp.where(idx < n, start + idx, -1)[None, :]
            last_idx = jnp.maximum(n - 1, 0)[None]
        cache, sampled = base(
            params, cache, tokens, positions, tables, last_idx, rng,
            temp, top_k, tp, seed, seats=slot[None],
        )
        with jax.named_scope("ctl"):
            S = last_tok.shape[0] - 1
            slot_eff = jnp.where(write > 0, slot, S)[None]
            last_tok = last_tok.at[slot_eff].set(sampled)
        return cache, last_tok, sampled

    return prefill


def make_packed_prefill_fn(cfg: ModelConfig, eng: EngineConfig,
                           T: int, W: int, mesh: Optional[Mesh] = None):
    return compilewatch.label(
        jax.jit(
            raw_packed_prefill_fn(cfg, eng, T, W, mesh),
            donate_argnums=(1, 2),
            **_io_kwargs(mesh, cfg, 3, ("cache", "repl", "repl"), eng=eng),
        ),
        f"packed_prefill_T{T}_W{W}",
    )


def make_ring_prefill_fn(cfg: ModelConfig, eng: EngineConfig,
                         mesh: Optional[Mesh] = None,
                         ring_mesh: Optional[Mesh] = None,
                         out_shardings=None):
    """Jitted ring prefill; cache + ring donated. ``out_shardings``
    overrides the canonical output layout if a caller needs to (the sp
    path's defaults already pin the serving cache layout)."""
    kw = _io_kwargs(mesh, cfg, 12, ("cache", "repl", "repl"), eng=eng)
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    return compilewatch.label(
        jax.jit(
            raw_ring_prefill_fn(cfg, eng, mesh, ring_mesh=ring_mesh),
            donate_argnums=(1, 2), **kw,
        ),
        "sp_ring_prefill" if ring_mesh is not None else "ring_prefill",
    )


def make_mm_prefill_fn(cfg: ModelConfig, eng: EngineConfig,
                       mesh: Optional[Mesh]):
    """Jitted multimodal prefill step: the regular unified step plus
    ``mm_embeds [B, T, D]`` / ``mm_mask [B, T]`` splicing precomputed
    vision embeddings over placeholder positions. Compiled lazily — only
    engines that actually see multimodal requests pay for it; decode
    never needs it (placeholders live in the prompt)."""
    refuse_unpaged(cfg, "the multimodal prefill")

    def step(params, cache, tokens, positions, block_tables,
             last_idx, rng, temperature, top_k, top_p, seeds,
             mm_embeds, mm_mask):
        cache, h = forward(
            cfg, eng, params, cache, tokens, positions, block_tables,
            mesh=mesh, mm_embeds=mm_embeds, mm_mask=mm_mask,
        )
        logits, pos_last = _last_logits(cfg, params, h, positions, last_idx)
        sampled = sample(
            logits, rng, temperature, top_k, top_p, seeds, pos_last
        )
        return cache, sampled

    return compilewatch.label(
        jax.jit(
            step, donate_argnums=(1,),
            **_io_kwargs(mesh, cfg, 11, ("cache", "repl"), eng=eng),
        ),
        "mm_prefill",
    )


def make_mm_ring_prefill_fn(cfg: ModelConfig, eng: EngineConfig,
                            mesh: Optional[Mesh]):
    """Ring-posting multimodal prefill (pipelined serving path): the mm
    step plus the ``last_tok`` write of ``make_ring_prefill_fn``."""
    refuse_unpaged(cfg, "the multimodal prefill")

    def step(params, cache, last_tok, tokens, positions, block_tables,
             last_idx, slot_ids, write_mask, rng,
             temperature, top_k, top_p, seeds, mm_embeds, mm_mask):
        cache, h = forward(
            cfg, eng, params, cache, tokens, positions, block_tables,
            mesh=mesh, mm_embeds=mm_embeds, mm_mask=mm_mask,
        )
        logits, pos_last = _last_logits(cfg, params, h, positions, last_idx)
        sampled = sample(
            logits, rng, temperature, top_k, top_p, seeds, pos_last
        )
        with jax.named_scope("ctl"):
            S = last_tok.shape[0] - 1
            slot_eff = jnp.where(write_mask > 0, slot_ids, S)
            last_tok = last_tok.at[slot_eff].set(sampled)
        return cache, last_tok, sampled

    return compilewatch.label(
        jax.jit(
            step, donate_argnums=(1, 2),
            **_io_kwargs(mesh, cfg, 14, ("cache", "repl", "repl"), eng=eng),
        ),
        "mm_ring_prefill",
    )


def make_sp_ring_prefill_fn(cfg: ModelConfig, eng: EngineConfig, mesh: Mesh):
    """Jitted full-prompt sequence-parallel prefill, posting to the ring.

    The ring runs over the SERVING mesh itself: the chunk's T axis is
    sharded over the composite (dp, tp) [..fsdp] axes (``SpecLayout.
    seq_axes``) — NOT over a second flat ``sp`` mesh on the same devices,
    which GSPMD could only reconcile with the head-sharded cache by fully
    rematerializing every crossing tensor (the MULTICHIP_r05 storm). The
    cache's out_shardings pin the serving layout so subsequent decode
    steps see an unchanged (donated) cache. SURVEY §5 long-context;
    exact — ring attention accumulates online softmax in f32.
    """
    return make_ring_prefill_fn(cfg, eng, mesh, ring_mesh=mesh)


# ------------------------ KV block transfer ops ---------------------------
#
# The disaggregated P→D data plane (role of the reference's NIXL transfer +
# block_copy.cu resharding kernels, ref: lib/llm/src/block_manager/
# distributed/transfer.rs, kernels/block_copy.cu:41): gather a sequence's
# physical blocks out of the paged cache / scatter received blocks into
# pre-allocated slots. XLA compiles these to fused gather/scatter; on TPU
# the same jitted fns ride ICI when source and destination share a mesh.


def cache_payload_keys(cfg: ModelConfig,
                       eng: EngineConfig) -> Tuple[str, ...]:
    """The cache dict keys a block transfer must carry: quantized caches
    add the per-(slot, head) scale planes to the K/V pages.  A table some
    of whose layers keep neither K nor V is refused: its blocks are not
    the whole of a sequence's memory."""
    refuse_unpaged(cfg, "a KV block transfer")
    if quant.is_quantized(eng.kv_dtype):
        return ("k", "v", "ks", "vs")
    return ("k", "v")


def make_kv_ops(cfg: ModelConfig, eng: EngineConfig,
                mesh: Optional[Mesh] = None):
    """(extract, inject) jitted block gather/scatter over the paged cache.

    extract(cache, block_ids[N]) -> {"k","v"}: [L, N, KV, bs, hd]
    (plus {"ks","vs"}: [L, N, KV, bs] when the cache is quantized)
    inject(cache, block_ids[N], data) -> cache  (donated, in-place scatter)

    In the block-major layout these are single-axis gathers/scatters over
    whole contiguous blocks — XLA lowers them to block-granular DMA. With
    a mesh, extract pins the transfer payload to ``SpecLayout.kv_blocks``
    (KV heads over tp — the same axis the cache shards) and inject pins
    the cache back to its serving layout, so the disagg handoff agrees
    with the cache about head placement on both ends.
    """
    keys = cache_payload_keys(cfg, eng)
    kw_ex: Dict[str, Any] = {}
    kw_in: Dict[str, Any] = {}
    if _multi(mesh):
        lay = SpecLayout.for_mesh(mesh)
        kw_ex["out_shardings"] = layout.kv_payload_shardings(mesh, keys)
        # inject returns the full per-layer cache dict: page layers pin to
        # the cache layout, scale layers to the scale layout
        page = NamedSharding(mesh, lay.cache_block())
        scale = NamedSharding(mesh, lay.cache_scale_block())
        kw_in["out_shardings"] = {
            key: (scale if key in ("ks", "vs") else page) for key in keys
        }

    def extract(cache: Cache, block_ids: jax.Array) -> Cache:
        return {
            key: jnp.stack([jnp.take(layer, block_ids, axis=0)
                            for layer in cache[key]])
            for key in keys
        }

    def inject(cache: Cache, block_ids: jax.Array, data: Cache) -> Cache:
        return {
            key: [layer.at[block_ids].set(data[key][li])
                  for li, layer in enumerate(cache[key])]
            for key in keys
        }

    return (
        # read-only gather: the serving engine keeps using the cache after
        # an extract, so donating it here would free live KV
        compilewatch.label(jax.jit(extract, **kw_ex), "kv_extract"),  # dynalint: disable=DT103
        compilewatch.label(
            jax.jit(inject, donate_argnums=(0,), **kw_in), "kv_inject"
        ),
    )
