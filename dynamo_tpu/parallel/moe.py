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

from ..utils.logging import get_logger

log = get_logger("moe")


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

# The default scoped VMEM of a Pallas call on the chip (16 MiB on a v5e):
# megablox ``gmm`` sets no limit of its own, so its blocks must fit it.
GMM_VMEM_BYTES = 16 * 2 ** 20


def gmm_block_bytes(tm: int, tk: int, tn: int, itemsize: int = 2) -> int:
    """VMEM one grouped-matmul step holds: the lhs ``[tm, tk]`` and rhs
    ``[tk, tn]`` blocks double-buffered, the float32 output block
    double-buffered, the accumulator and the product before it is added
    (Mosaic took (128, 3072, 1024) and refused (512, 3072, 512), which this
    count puts 0.5 MiB under and exactly at the limit; it also took
    (128, 1024, 3072), which the count puts over: it errs to the safe
    side)."""
    return 2 * (tm * tk + tk * tn) * itemsize + 4 * tm * tn * 4


def _cuts(dim: int):
    """``dim`` and its divisors that are whole lane tiles, largest first."""
    return [dim] + [d for d in range(dim - 128, 0, -128)
                    if dim % d == 0 and d % 128 == 0]


def gmm_tile(rows: int, k: int, n: int, itemsize: int = 2):
    """The grouped matmul's ``(tm, tk, tn)`` for ``rows`` sorted pairs times
    ``[groups, k, n]``, from the shape alone (static at trace time).

    tk = K wherever a block of K fits the scoped VMEM: gmm's grid is
    (n tiles, visits, k tiles) and a weight block's index (group, k_i, n_i),
    so with one k step a group that spans two row tiles keeps its block
    between the visits, and with three it is fetched again; a K that the
    tile does not divide (2560 by 1024) also costs a masked remainder step.
    tn = N, else its largest divisor of whole lane tiles that fits, 512 at
    least.  tm = 64 up to 1024 rows (a decode window: a group holds a few
    rows, half the row tile halves a visit's matmul and lhs block and adds
    hardly a visit), 128 beyond (a chunk: 64 doubles the row tiles).

    Read on the chip (PERF.md, PR 40), us a call = group metadata + kernel,
    medians.  "all E": the parent's form (sizes over all routed experts, a
    group offset, gmm's zeroing select) at PR 32's tile (128, 1024, 1024),
    k and n cut to the matrix; "held": the held sizes alone at that tile;
    then the tile chosen here:

    (pairs, K, N), touched         all E   held  chosen
    (320, 3072, 1024), 93 of 128     931    927  (64,3072,1024) 913-922
    (320, 1024, 3072)                932    919  (64,1024,3072) 921-924
    (5120, 3072, 1024), 128         1575   1499  (128,3072,1024) 1384-1403
    (5120, 1024, 3072)              1594   1367  (128,1024,1536) 1351
    (1024, 2560, 768), 29 of 64      363    331  (64,2560,768) 287-302
    (1024, 768, 2560)                341    327  (64,768,2560) 279-293
    (4096, 2560, 768), 44            580    467  (128,2560,768) 419-429
    (4096, 768, 2560)                552    424  (128,768,2560) 377-388

    Beside them, at 5120 pairs: (128,3072,512) 1413, (128,1536,1024) 1491,
    (128,1024,3072) 1325-1359, tm 256 1463-1518, tm 64 1413-1425 and
    1362-1364; at 4096 pairs tm 64 equals 128; at 1024 pairs tm 128
    304-321 and 293-316, tm 32 291-292 and 280-282; tn = N/2 within 10 us
    of tn = N everywhere.  At 320 pairs no tile differs by more than the
    runs do: PR 32's, read at 8 rows (1.01 ms), is as fast there as any
    and loses only at a chunk's rows."""
    tm = 64 if rows <= 1024 else 128
    for tk in _cuts(k):
        for tn in (c for c in _cuts(n) if c >= min(n, 512)):
            if gmm_block_bytes(tm, tk, tn, itemsize) < GMM_VMEM_BYTES:
                return tm, tk, tn
    raise ValueError(f"no grouped-matmul tile of [{k}, {n}] fits the "
                     f"scoped VMEM")


# the room a slab of rows has over the held experts' even share of the pairs
HELD_ROOM = 2
# a call's rows follow the held pairs where a slab is at most one part in
# HELD_SHARE of all its pairs
HELD_SHARE = 8


def held_rows(pairs: int, held: int, width: int, tm: int) -> int:
    """The rows a sparse call is shaped for, from the shapes alone (static
    at trace time): ``HELD_ROOM`` times the even share that ``held`` of the
    router's ``width`` outputs (routed and zero-compute) get of the
    ``pairs`` sorted pairs, one row tile ``tm`` at least, in whole row
    tiles: a slab.  It is room, not a capacity: ``routed_ffn`` walks the
    held pairs slab by slab, one slab nearly always, and drops none.

    The one rule of engagement: where a slab is more than one part in
    ``HELD_SHARE`` of the pairs the rows are all the pairs in whole tiles,
    and ``routed_ffn`` traces the body it always had.

    (pairs, held of width, tm) -> rows: a chunk of 512 tokens and the
    largest decode window of the benchmark's three tables:
    (6144, 16 of 768, 128) -> 256      (768, 16 of 768, 64) -> 64
    (4096, 64 of 512, 128) -> 4096     (1024, 64 of 512, 64) -> 1024
    (5120, 128 of 256, 128) -> 5120    (320, 128 of 256, 64) -> 320

    Read on the chip (PERF.md, PR 43), us a call = router + sorts +
    experts of one layer at real widths, medians of 30 device spans; "all
    pairs": the body as it was; then slabs, the rule forced where it does
    not engage; "(a)": the same rows under a ``cond`` whose other branch is
    the worst case's body, the form this loop was chosen over:

    (tokens, held of width)      all pairs  slabs of
    (512, 16 of 768) agent         4429     256: 2047  512: 2096  (a) 2080, 2117
    (64, 16 of 768)                 882      64:  793             (a)  808
    (512, 64 of 512) longgen       1267    1024: 1158  1536: 1194
    (128, 64 of 512)                873     256:  836   384:  850

    A slab of a 24th or a 12th of the pairs takes 54% and 10% off a call;
    one of a quarter 9% and 4% (108 and 37 us), which is under what
    longgen's runs can tell apart: hence one part in 8, and longgen's and
    codegen's programs stay text for text what they were.  Room: at the
    model's own hidden states a layer's T=512 call held 15 to 284 pairs
    (mean 80-143 by layer and seed; an even draw would give 128 +- 11) and
    a 64-row step 14 +- 4, at most 22.  A second pass over a few rows costs
    ~150 us (a slab of 128 under 141 held pairs: 2193), twice the rows
    ~50 us every call: so twice the share, not four times."""
    up = lambda n: -(-n // tm) * tm                          # noqa: E731
    slab = up(max(-(-HELD_ROOM * pairs * held // width), 1))
    return slab if HELD_SHARE * slab <= pairs else up(pairs)


# (pairs, rows, K, N) -> (tm, tk, tn) of every distinct grouped-matmul call
# traced in this process (one serving engine a process, as
# ATTENTION_TRACES): ``InferenceEngine.device_report`` shows it under
# ``/health``.  ``rows`` < ``pairs``: a slab of ``held_rows``
GMM_TILES_TRACED: Dict[tuple, tuple] = {}

# the counters a sparse layer hands back, in ``routed_ffn``'s order: four
# sums, the one maximum, then a sum that only a call whose rows follow the
# held pairs hands back (``model.moe_stats_row`` reduces them so, and a
# window without it reads 0): the held pairs beyond the call's first slab
MOE_STATS = ("moe_pairs", "moe_pairs_held", "moe_experts_touched",
             "moe_pairs_zero", "moe_load_max", "moe_pairs_overflow")


def route(x: jax.Array, w_router: jax.Array, *, top_k: int,
          renormalise: bool, scale: float, score: str = "softmax",
          bias: jax.Array | None = None, n_group: int = 0,
          topk_group: int = 0):
    """The router in float32 over every router output, routed and
    zero-compute (``w_router``'s whole width): the ``top_k`` outputs of each
    token ``[N, k]`` and their weights on the experts' outputs, ``scale *
    s_e`` over ``sum_top s`` where ``renormalise`` (the sum over all the
    chosen, never over the ones held), else ``scale * s_e`` as scored.

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
    w_router: jax.Array,   # [D, E] every router output: routed, then zero
    w_gate: jax.Array,     # [Eh, D, F] the experts held here
    w_up: jax.Array,       # [Eh, D, F]
    w_down: jax.Array,     # [Eh, F, D]
    *,
    top_k: int,
    held_start: int,       # w_gate[0] is routed expert ``held_start``
    renormalise: bool = True,
    scale: float = 1.0,
    live: jax.Array | None = None,   # [N] bool; dead rows route nowhere
    n_zero: int = 0,       # the router's last ``n_zero`` are identities
    interpret: bool = False,
    **router,              # route's score / bias / n_group / topk_group
):
    """What the experts held here add for the tokens routed to them:
    ``sum_{e in top_k(x) and held} w_e * swiglu_e(x)``, ``[N, D]``; the
    ``MOE_STATS`` as int32; and every token's ``top_k`` choices over every
    router output, routed and zero-compute, ``[N, top_k]`` int32 (what a
    reference check reads: a top-k is a discrete choice).

    With ``n_zero`` > 0 a choice ``e >= E - n_zero`` is a zero-compute
    expert, an identity: its pair adds ``w_e * x`` and costs no expert
    bytes, on this shard as on every other (a deployment computes it where
    the token lives, without an exchange).  The sum of a live token's
    identity weights times the token is added here, under ``moe_zero``.

    Every token keeps all its experts: there is no capacity.  The
    ``N * top_k`` (token, expert) pairs are sorted by expert; pairs of
    experts not held, identity pairs, and those of dead rows, sort behind
    every held group into rows no group owns, which the grouped matmuls
    neither read weights for nor compute: a token whose choices are all
    identities adds no row to a group.  The grouped matmul (megablox
    ``gmm``) visits only the (group, row tile) pairs that hold a row, so an
    expert no token chose is not read.

    Shapes are static.  Where the held experts are a small share of the
    router's width (``held_rows``) what follows the sort (the gather, the
    three grouped matmuls, the select, the weights and the sum into ``[N,
    D]``) is shaped for a slab of ``held_rows`` sorted pairs and walks the
    held pairs, which sort first, slab by slab under a loop of
    ``ceil(held pairs / slab)`` passes: one nearly always, none where no
    pair is held, and as many as it takes otherwise, so no pair is dropped
    (``moe_pairs_overflow`` counts the held pairs behind the first slab).
    Everywhere else the rows are the worst case's ``N * top_k`` and there
    is no loop: the program is the one it was.
    One device: an ``ep`` mesh would exchange tokens before and after, and
    this layer has no such exchange (PERF.md, Open questions); the exchange
    would hand this shard the held pairs alone, which is what a slab stands
    in for."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    N, D = x.shape
    E = w_router.shape[1]
    Eh, _, F = w_gate.shape
    dt = x.dtype
    pairs = N * top_k
    tm = gmm_tile(pairs, D, F, w_gate.dtype.itemsize)[0]
    rows = held_rows(pairs, Eh, E, tm)
    slabs = rows < pairs
    tiles = {(K, Nn): gmm_tile(rows, K, Nn, w_gate.dtype.itemsize)
             for K, Nn in ((D, F), (F, D))}
    for (K, Nn), tile in tiles.items():
        if (pairs, rows, K, Nn) not in GMM_TILES_TRACED:
            GMM_TILES_TRACED[pairs, rows, K, Nn] = tile
            log.info("grouped matmul (rows %d of %d pairs, K %d, N %d) = "
                     "[%d, %d] @ [%d, %d, %d]: tile %s", rows, pairs, K, Nn,
                     rows, K, Eh, K, Nn, tile)

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
        zero = alive & (expert >= E - n_zero)
        stats = [
            jnp.sum(alive), jnp.sum(held), jnp.sum(here > 0), jnp.sum(zero),
            jnp.max(here),
        ]
        n_held = stats[1]
        if slabs:
            stats.append(jnp.maximum(n_held - rows, 0))
        stats = jnp.stack(stats).astype(jnp.int32)

    # the held groups alone, from row 0 on: metadata over Eh groups and no
    # offset, so gmm neither rolls it nor zeroes the rows behind them
    mm = functools.partial(gmm, preferred_element_type=jnp.float32,
                           interpret=interpret)

    def swiglu(xs, group_sizes):
        gate = jax.nn.silu(mm(xs, w_gate, group_sizes, tiling=tiles[D, F]))
        up = mm(xs, w_up, group_sizes, tiling=tiles[D, F])
        return mm((gate * up).astype(dt), w_down, group_sizes,
                  tiling=tiles[F, D])                        # [rows, D] f32

    if not slabs:
        with jax.named_scope("moe_experts"):
            xs = jnp.take(x, token, axis=0)
            xs = jnp.pad(xs, ((0, rows - pairs), (0, 0)))
            y = swiglu(xs, here)[:pairs]                     # [pairs, D] f32
            # rows of no group come back as they were left (whatever the
            # VMEM held, a NaN too): select, not multiply
            w_sorted = jnp.take(top_w.reshape(pairs), order)
            y = jnp.where(held_sorted[:, None], y * w_sorted[:, None], 0.0)
            # back to (token, slot) order, then the sum over a token's slots
            y = jnp.take(y, jnp.argsort(order), axis=0)
            out = jnp.sum(y.reshape(N, top_k, D), axis=1)
    else:
        with jax.named_scope("moe_experts"):
            ends = jnp.cumsum(here)
            # a last slab may reach past the pairs
            behind = jnp.pad(order, (0, rows))

        def slab(i, out):
            """Adds the sorted pairs ``[i * rows, (i + 1) * rows)``: a
            group's rows in the slab are its clipped ends; the sum over a
            token's rows is a one-hot ``[N, rows] @ [rows, D]`` in float32
            ``HIGHEST`` (the MXU adds them, as exactly as float32 adds: a
            product by 0 or 1)."""
            with jax.named_scope("moe_experts"):
                first = i * rows
                inside = lambda e: jnp.clip(e - first, 0, rows)  # noqa: E731
                at = jax.lax.dynamic_slice(behind, (first,), (rows,))
                tok = at // top_k
                y = swiglu(jnp.take(x, tok, axis=0),
                           inside(ends) - inside(ends - here))
                # as above: select, not multiply
                sel = first + jnp.arange(rows) < n_held
                w_sorted = jnp.take(top_w.reshape(pairs), at)
                y = jnp.where(sel[:, None], y * w_sorted[:, None], 0.0)
                hot = jnp.arange(N)[:, None] == tok[None, :]
                return out + jnp.dot(hot.astype(jnp.float32), y,
                                     precision=jax.lax.Precision.HIGHEST)

        # the loop's own op stays outside the scope: a trace counts it
        # beside the ops of its body
        out = jax.lax.fori_loop(0, (n_held + rows - 1) // rows, slab,
                                jnp.zeros((N, D), jnp.float32))
    if n_zero:
        with jax.named_scope("moe_zero"):
            w_zero = jnp.sum(jnp.where(zero.reshape(N, top_k), top_w, 0.0),
                             axis=1)
            out = out + w_zero[:, None] * x.astype(jnp.float32)
    return out.astype(dt), stats, top_idx
