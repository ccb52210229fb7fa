"""Mixture-of-Experts FFN with expert parallelism.

WideEP-class capability (ref: the reference's pass-through EP flags,
components/backends/sglang/docs/dsr1-wideep-h100.md — engine-internal there,
first-class here). GShard-style capacity-based dispatch, built entirely from
one-hot matmuls and batched einsums so everything lands on the MXU and the
GSPMD partitioner shards it over the expert mesh axis with automatic
all-to-alls — no per-token gather/scatter, no dynamic shapes.

Sharding contract: expert-stacked weights ``[E, D, F]`` carry
``P("ep"|"tp", None, None)``; the dispatch/combine einsums contract over the
token axis, so XLA materialises per-expert buffers ``[E, C, D]`` sharded over
E — each device computes only its experts.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def moe_capacity(num_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-expert token capacity (static at trace time)."""
    return max(1, math.ceil(num_tokens * top_k / num_experts
                            * capacity_factor))


def moe_ffn(
    x: jax.Array,          # [N, D] tokens (flattened batch)
    w_router: jax.Array,   # [D, E]
    w_gate: jax.Array,     # [E, D, F]
    w_up: jax.Array,       # [E, D, F]
    w_down: jax.Array,     # [E, F, D]
    *,
    top_k: int,
    capacity_factor: float = 2.0,
) -> jax.Array:
    """Top-k routed SwiGLU experts; returns [N, D].

    Tokens overflowing an expert's capacity lose that expert's contribution
    (their combine weight is zeroed and the rest renormalised) — standard
    GShard semantics; raise ``capacity_factor`` for exactness.
    """
    N, D = x.shape
    E = w_router.shape[1]
    C = moe_capacity(N, E, top_k, capacity_factor)
    dt = x.dtype

    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)      # [N, k]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # one-hot expert assignment per (token, slot): [N, k, E]
    assign = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
    # position of each (token, slot) within its expert's capacity buffer:
    # running count of prior assignments to the same expert, flattened over
    # (token-major, slot-minor) order
    flat = assign.reshape(N * top_k, E)
    pos = jnp.cumsum(flat, axis=0) - flat                # [N*k, E]
    pos = jnp.sum(pos * flat, axis=-1).reshape(N, top_k)  # [N, k]
    in_cap = pos < C
    gates = jnp.where(in_cap, top_vals, 0.0)             # [N, k]

    # dispatch tensor [N, E, C]: token n -> (expert, capacity slot)
    pos_hot = jax.nn.one_hot(
        jnp.where(in_cap, pos, C), C, dtype=jnp.float32
    )                                                     # [N, k, C]
    dispatch = jnp.einsum("nke,nkc->nec", assign, pos_hot)
    combine = jnp.einsum("nke,nkc,nk->nec", assign, pos_hot, gates)

    xin = jnp.einsum("nec,nd->ecd", dispatch, x.astype(jnp.float32))
    xin = xin.astype(dt)                                  # [E, C, D]
    gate = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xin, w_gate).astype(jnp.float32)
    )
    up = jnp.einsum("ecd,edf->ecf", xin, w_up).astype(jnp.float32)
    h = (gate * up).astype(dt)
    out = jnp.einsum("ecf,efd->ecd", h, w_down)           # [E, C, D]
    return jnp.einsum(
        "nec,ecd->nd", combine, out.astype(jnp.float32)
    ).astype(dt)


# ---------------------------------------------------------------------------
# Routing without drops over the experts this shard holds (a table's sparse
# layers).  moe_ffn above stays for a model without a table; that there are
# two is debt (ROADMAP.md, Queue 3).

# the grouped matmul's (m, k, n) tile for x @ [D, F] and h @ [F, D]; the
# sorted pairs are padded to a multiple of m.  Read on the chip at D 3072,
# F 1024, 8 rows, 38 experts touched (PERF.md, PR 32): 1.01 ms = 710 GB/s of
# expert weights, against 1.02-1.10 for ten other tilings with k, n >= 512
# and 1.18 for three ``jax.lax.ragged_dot``
GMM_TILE = (128, 1024, 1024)

MOE_STATS = ("moe_pairs", "moe_pairs_held", "moe_experts_touched",
             "moe_load_max")


def _tile(k: int, n: int):
    """``GMM_TILE`` with its k and n tiles no larger than the matrix."""
    return (GMM_TILE[0], min(GMM_TILE[1], k), min(GMM_TILE[2], n))


def route(x: jax.Array, w_router: jax.Array, *, top_k: int,
          renormalise: bool, scale: float, score: str = "softmax",
          bias: jax.Array | None = None, n_group: int = 0,
          topk_group: int = 0):
    """The router in float32 over ALL routed experts: the ``top_k`` experts
    of each token ``[N, k]`` and their weights on the experts' outputs,
    ``scale * s_e / sum_top s`` (the sum over the chosen of all routed
    experts, never over the ones held).

    ``score``: the scores ``s`` are a softmax over the experts, or a
    sigmoid of each logit.  ``bias [E]`` is added to the scores for the
    choice alone; the weights are the chosen experts' own scores.
    ``n_group`` > 0 limits the choice to ``topk_group`` of ``n_group``
    equal ranges of experts, a group scored by the sum of its two largest
    (biased) scores: the devices a token may visit, where a group is a
    device's (DeepSeek-V3's ``noaux_tc``)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
             else jax.nn.sigmoid(logits))
    pick = probs if bias is None else probs + bias.astype(jnp.float32)
    if n_group:
        N, E = pick.shape
        groups = pick.reshape(N, n_group, E // n_group)
        best2 = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(best2, topk_group)             # [N, tg]
        kept = jnp.zeros((N, n_group), bool).at[
            jnp.arange(N)[:, None], keep].set(True)
        pick = jnp.where(jnp.repeat(kept, E // n_group, axis=1),
                         pick, -jnp.inf)
    _, top_idx = jax.lax.top_k(pick, top_k)
    top_vals = jnp.take_along_axis(probs, top_idx, axis=1)
    if renormalise:
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    return top_idx, top_vals * scale


def routed_ffn(
    x: jax.Array,          # [N, D] tokens (flattened batch)
    w_router: jax.Array,   # [D, E] over every routed expert
    w_gate: jax.Array,     # [Eh, D, F] the experts held here
    w_up: jax.Array,       # [Eh, D, F]
    w_down: jax.Array,     # [Eh, F, D]
    *,
    top_k: int,
    held_start: int,       # w_gate[0] is routed expert ``held_start``
    renormalise: bool = True,
    scale: float = 1.0,
    live: jax.Array | None = None,   # [N] bool; dead rows route nowhere
    interpret: bool = False,
    **router,              # route's score / bias / n_group / topk_group
):
    """What the experts held here add for the tokens routed to them:
    ``sum_{e in top_k(x) and held} w_e * swiglu_e(x)``, ``[N, D]``; the
    four ``MOE_STATS`` as int32; and every token's ``top_k`` choices among
    all routed experts, ``[N, top_k]`` int32 (what a reference check reads:
    a top-k is a discrete choice).

    Every token keeps all its experts: there is no capacity.  The
    ``N * top_k`` (token, expert) pairs are sorted by expert; pairs of
    experts not held, and of dead rows, sort behind every held group into
    rows no group owns, which the grouped matmuls neither read weights for
    nor compute.  The grouped matmul (megablox ``gmm``) visits only the
    (group, row tile) pairs that hold a row, so an expert no token chose is
    not read.  Shapes are static at the worst case of ``N * top_k`` rows.
    One device: an ``ep`` mesh would exchange tokens before and after, and
    this layer has no such exchange (PERF.md, Open questions)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    N, D = x.shape
    E = w_router.shape[1]
    Eh, _, F = w_gate.shape
    dt = x.dtype
    pairs = N * top_k
    rows = -(-pairs // GMM_TILE[0]) * GMM_TILE[0]

    with jax.named_scope("moe_router"):
        top_idx, top_w = route(x, w_router, top_k=top_k,
                               renormalise=renormalise, scale=scale,
                               **router)
        expert = top_idx.reshape(pairs)
        alive = (jnp.ones((pairs,), bool) if live is None
                 else jnp.repeat(live, top_k))
        held = alive & (expert >= held_start) & (expert < held_start + Eh)
        # held pairs by expert, the rest behind them under the key E
        key = jnp.where(held, expert, E)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
        token = order // top_k
        held_sorted = jnp.take(held, order)
        here = sizes[held_start:held_start + Eh]
        stats = jnp.stack([
            jnp.sum(alive), jnp.sum(held), jnp.sum(here > 0), jnp.max(here),
        ]).astype(jnp.int32)

    with jax.named_scope("moe_experts"):
        xs = jnp.take(x, token, axis=0)
        xs = jnp.pad(xs, ((0, rows - pairs), (0, 0)))
        start = jnp.asarray(held_start, jnp.int32)
        mm = functools.partial(gmm, group_sizes=sizes,
                               preferred_element_type=jnp.float32,
                               group_offset=start, interpret=interpret)
        gate = jax.nn.silu(mm(xs, w_gate, tiling=_tile(D, F)))
        up = mm(xs, w_up, tiling=_tile(D, F))
        y = mm((gate * up).astype(dt), w_down,
               tiling=_tile(F, D))[:pairs]                   # [pairs, D] f32
        # rows of no group come back as they were left: select, not multiply
        w_sorted = jnp.take(top_w.reshape(pairs), order)
        y = jnp.where(held_sorted[:, None], y * w_sorted[:, None], 0.0)
        # back to (token, slot) order, then the sum over a token's slots
        y = jnp.take(y, jnp.argsort(order), axis=0)
        out = jnp.sum(y.reshape(N, top_k, D), axis=1).astype(dt)
    return out, stats, top_idx
