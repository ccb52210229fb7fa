"""Pallas TPU ragged paged-attention kernel.

ONE kernel serves every attention shape the engine dispatches against the
paged KV cache — following the *Ragged Paged Attention* design (PAPERS.md):

- decode rows (``q_len == 1``) — the serving hot loop,
- spec-verify windows (``q_len == k+1``, ``engine.model.raw_spec_window_fn``),
- prefill chunks (``q_len`` up to the chunk budget),

all mixed in one launch.  Queries are packed along a single flat axis; each
row ``r`` owns the slots ``[q_start[r], q_start[r+1])`` and fills the first
``q_len[r]`` of them.  The per-row ``(q_start, q_len, ctx_len)`` metadata and
the block tables ride ``PrefetchScalarGridSpec`` scalar prefetch, so the K/V
``BlockSpec`` index maps *read the block table* to pick which physical block
Mosaic DMAs next — the pipeline does the paged gather for free, double-
buffered, overlapping the previous block's FLOPs.  All KV heads of a page
travel in one ``[KV, bs, hd]`` block (one contiguous DMA, few large grid
steps — a per-(b, kv, w) grid was measured 8× slower from per-step
overheads).  Flash-style online softmax keeps nothing materialised; per-row
causal masking makes query ``i`` of row ``r`` (absolute position
``ctx_len - q_len + i``) see exactly the keys at positions ``<= that``.

Trash-block contract (physical block 0): the scheduler never allocates
block 0 and scatters every padding write into it, so its contents are
arbitrary.  The kernel guarantees that rows with ``q_len == 0`` (freshly
reset seats, padding rows) and key slots at positions ``>= ctx_len``
(partial last blocks, stale table tails) contribute *exactly zero* and can
never NaN-poison the online softmax: masked K/V is zeroed before the MXU,
masked scores go to ``-inf`` behind a finite-max guard, and a zero softmax
denominator divides as 1 — dead rows emit exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _row_tile(t, q_start_ref, r, q_tile):
    """Clamp grid q-tile ``t`` into row ``r``'s own allotment.

    A row owns ``(q_start[r+1] - q_start[r]) // q_tile`` tiles; grid steps
    past that are no-ops but must still map somewhere — clamping keeps them
    inside the row so they can never clobber a neighbour's output block.
    """
    alloc = (q_start_ref[r + 1] - q_start_ref[r]) // q_tile
    t_eff = jnp.minimum(t, jnp.maximum(alloc - 1, 0))
    return alloc, t_eff


def _ragged_kernel(
    # scalar prefetch
    q_start_ref,   # [R+1] int32 flat q slot of each row (multiples of TQ)
    q_len_ref,     # [R] int32 valid queries per row (0 = dead row)
    ctx_len_ref,   # [R] int32 context length incl. the row's fed tokens
    tables_ref,    # [R, W] int32 physical block ids (0 = trash)
    # blocks
    q_ref,         # [KV, TQ, G, hd]
    k_ref,         # [1, KV, kv_tile, hd]
    v_ref,         # [1, KV, kv_tile, hd]
    # quantized kv_dtype adds two scale blocks here: ks_ref/vs_ref
    # [1, KV, kv_tile] f32 (see *rest unpacking below)
    *rest,
    kv_tile: int,
    q_tile: int,
    scale: float,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    r = pl.program_id(0)
    t = pl.program_id(1)
    w = pl.program_id(2)
    num_w = pl.num_programs(2)
    # grid step w covers absolute key positions [w*kv_tile, (w+1)*kv_tile):
    # when kv_tile sub-splits a physical block, consecutive w walk its
    # sub-tiles in order, so the online-softmax math below is oblivious to
    # whether a step is a whole block or a slice of one.
    bs = kv_tile

    q_len = q_len_ref[r]
    ctx_len = ctx_len_ref[r]
    alloc, t_eff = _row_tile(t, q_start_ref, r, q_tile)
    in_row = t < alloc                 # this step owns an output tile
    live = t_eff * q_tile < q_len      # ... with at least one valid query
    # highest key position any query of this tile may see
    last_q = jnp.minimum((t_eff + 1) * q_tile, q_len) - 1
    max_vis = ctx_len - q_len + last_q

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(in_row & live & (w * bs <= max_vis))
    def _compute():
        KV, TQ, G, hd = q_ref.shape
        q = q_ref[...].astype(jnp.float32).reshape(KV, TQ * G, hd)
        k = k_ref[0].astype(jnp.float32)                 # [KV, bs, hd]
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            # quantized pages: dequantize with the per-(slot, head) scales
            # BEFORE the trash-slot zeroing below, so arbitrary bits in the
            # trash block's scale rows (NaN included) are wiped by the same
            # jnp.where that wipes the page payload.
            k = k * ks_ref[0].astype(jnp.float32)[..., None]
            v = v * vs_ref[0].astype(jnp.float32)[..., None]
        # keys at positions >= ctx_len live in the trash block / a stale
        # table tail — their bits are arbitrary (NaN included).  Zero them
        # BEFORE the MXU: -inf score masking alone still lets NaN·0 leak
        # through the p@v product.
        kpos = w * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, bs, 1), dimension=1
        )                                                # [1, bs, 1]
        kvalid = kpos < ctx_len
        k = jnp.where(kvalid, k, 0.0)
        v = jnp.where(kvalid, v, 0.0)

        # batched over KV heads: [KV, TQ*G, hd] x [KV, bs, hd] -> s
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [KV, TQ*G, bs]

        # per-query causal mask: flat row j is query t_eff*TQ + j//G at
        # absolute position ctx_len - q_len + that
        qi = t_eff * q_tile + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1
        ) // G
        spos = w * bs + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=2
        )
        valid = (qi < q_len) & (spos <= ctx_len - q_len + qi)
        s = jnp.where(valid, s, -jnp.inf)

        m_prev = m_ref[...]                              # [KV, TQ*G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # m_new can only be -inf while no valid key has been seen; the
        # guard keeps exp() finite for fully-masked query rows.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe,
                                  -jnp.inf))             # [KV, TQ*G, 1]
        p = jnp.exp(s - m_safe)                          # [KV, TQ*G, bs]
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # [KV, TQ*G, hd]

    @pl.when((w == num_w - 1) & in_row & (t == t_eff))
    def _finalize():
        KV, TQ, G, hd = o_ref.shape
        l = l_ref[...]
        # Fully-masked query rows (q_len == 0 seats, tile tails) keep
        # l == 0 → emit exact zeros, never NaN.
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = out.reshape(KV, TQ, G, hd).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "q_tile", "kv_tile", "max_q_len",
                     "interpret"),
)
def paged_attention_ragged(
    q: jax.Array,             # [Tq, H, hd] flat packed queries
    k_cache: jax.Array,       # [num_blocks, KV, bs, hd] paged cache
    v_cache: jax.Array,       # [num_blocks, KV, bs, hd]
    block_tables: jax.Array,  # [R, W] int32 (0 = trash block)
    q_start: jax.Array,       # [R+1] int32, q_start[R] == Tq
    q_len: jax.Array,         # [R] int32 (0 = dead/padding row)
    ctx_len: jax.Array,       # [R] int32 context incl. the row's own tokens
    *,
    block_size: int,
    max_q_len: int,
    q_tile: int = 0,
    kv_tile: int = 0,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # [num_blocks, KV, bs] f32
    v_scale: jax.Array | None = None,  # [num_blocks, KV, bs] f32
) -> jax.Array:
    """Ragged paged attention over heterogeneous-length query rows.

    Row ``r`` owns flat query slots ``[q_start[r], q_start[r+1])`` (both
    multiples of ``q_tile``, at least one tile per row); the first
    ``q_len[r]`` slots are its queries at absolute positions
    ``ctx_len[r] - q_len[r] .. ctx_len[r] - 1``, whose K/V must already be
    scattered into the cache (how ``engine.model.forward`` orders things).
    ``max_q_len`` (static) bounds ``q_start[r+1] - q_start[r]``.  Returns
    ``[Tq, H, hd]``; slots past ``q_len[r]`` but inside an allotted tile
    that holds at least one valid query — and every slot of a dead row —
    come back as exact zeros.

    ``(q_tile, kv_tile)`` are pure performance knobs (``engine.autotune``
    sweeps them per shape class): ``q_tile`` sets the output tile height,
    ``kv_tile`` the per-grid-step key window.  ``kv_tile`` must divide
    ``block_size``; values below it sub-split each physical block into
    ``block_size // kv_tile`` grid steps that DMA consecutive slices of the
    same block (paged tables are non-contiguous, so a step can never span
    *more* than one block — tuning upward means growing ``block_size``
    itself, a cache-layout change the autotuner only ever recommends).
    ``0`` means the default (``min(max_q_len, 128)`` / ``block_size``).

    Quantized KV (``EngineConfig.kv_dtype`` int8/fp8): pass the per-(slot,
    head) float32 scale caches as ``k_scale``/``v_scale`` — the kernel
    dequantizes each K/V tile inside the launch (one multiply before the
    MXU), with the scales riding two extra block inputs whose index map is
    the 3-tuple analogue of the page ``kv_map`` (same trash-block routing,
    so skipped steps DMA block 0's scales and the in-kernel zeroing wipes
    them along with the payload).  ``None`` (the default) traces the exact
    unquantized kernel — byte-identical to the pre-quant path.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    Tq, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    R, W = block_tables.shape
    bs = block_size
    if q_tile <= 0:
        q_tile = min(max_q_len, 128) if max_q_len % min(max_q_len, 128) == 0 \
            else max_q_len
    if max_q_len % q_tile or Tq % q_tile:
        raise ValueError(
            f"q_tile {q_tile} must divide max_q_len {max_q_len} and Tq {Tq}"
        )
    if kv_tile <= 0:
        kv_tile = bs
    if bs % kv_tile:
        raise ValueError(
            f"kv_tile {kv_tile} must divide block_size {bs}"
        )
    splits = bs // kv_tile
    num_t = max_q_len // q_tile

    # head-packed flat layout: [KV, Tq, G, hd] so a q tile is one
    # contiguous (KV, TQ, G, hd) block
    q4 = q.reshape(Tq, KV, G, hd).transpose(1, 0, 2, 3)

    def q_map(r, t, w, q_start, q_len, ctx_len, tables):
        _, t_eff = _row_tile(t, q_start, r, q_tile)
        return (0, q_start[r] // q_tile + t_eff, 0, 0)

    def kv_map(r, t, w, q_start, q_len, ctx_len, tables):
        # steps that do no work (dead tile, block past the tile's causal
        # frontier) DMA the always-resident trash block instead of real KV.
        # w walks sub-tiles: physical block w // splits, slice w % splits.
        alloc, t_eff = _row_tile(t, q_start, r, q_tile)
        live = (t < alloc) & (t_eff * q_tile < q_len[r])
        last_q = jnp.minimum((t_eff + 1) * q_tile, q_len[r]) - 1
        use = live & (w * kv_tile <= ctx_len[r] - q_len[r] + last_q)
        return (jnp.where(use, tables[r, w // splits], 0), 0, w % splits, 0)

    def scale_map(r, t, w, q_start, q_len, ctx_len, tables):
        # 3-tuple twin of kv_map for the [num_blocks, KV, bs] scale caches
        block, _, sub, _ = kv_map(r, t, w, q_start, q_len, ctx_len, tables)
        return (block, 0, sub)

    in_specs = [
        pl.BlockSpec((KV, q_tile, G, hd), q_map),
        pl.BlockSpec((1, KV, kv_tile, hd), kv_map),
        pl.BlockSpec((1, KV, kv_tile, hd), kv_map),
    ]
    operands = [q4, k_cache, v_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, KV, kv_tile), scale_map),
            pl.BlockSpec((1, KV, kv_tile), scale_map),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R, num_t, W * splits),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((KV, q_tile, G, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, q_tile * G, 1), jnp.float32),
            pltpu.VMEM((KV, q_tile * G, 1), jnp.float32),
            pltpu.VMEM((KV, q_tile * G, hd), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _ragged_kernel, kv_tile=kv_tile, q_tile=q_tile,
        scale=1.0 / (hd ** 0.5), quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, Tq, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q_start, q_len, ctx_len, block_tables, *operands)
    return out.transpose(1, 0, 2, 3).reshape(Tq, H, hd)


@functools.partial(
    jax.jit, static_argnames=("block_size", "kv_tile", "interpret")
)
def paged_attention_decode(
    q: jax.Array,          # [B, H, hd]
    k_cache: jax.Array,    # [num_blocks, KV, bs, hd] block-major paged cache
    v_cache: jax.Array,    # [num_blocks, KV, bs, hd]
    block_tables: jax.Array,  # [B, W] int32 (0 = trash block)
    seq_lens: jax.Array,      # [B] int32 (0 = padding row)
    *,
    block_size: int,
    kv_tile: int = 0,
    interpret: bool = False,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token-per-sequence paged attention.  Returns ``[B, H, hd]``.

    The decode face of the ragged kernel: every row is one query slot
    (``q_tile == 1``).  ``seq_lens[b]`` counts the valid context slots for
    row ``b`` *including* the token being decoded; ``seq_lens[b] == 0``
    rows emit exact zeros.  ``k_scale``/``v_scale`` carry quantized-KV
    dequant scales exactly as in :func:`paged_attention_ragged`.
    """
    B = q.shape[0]
    q_start = jnp.arange(B + 1, dtype=jnp.int32)
    q_len = (seq_lens > 0).astype(jnp.int32)
    return paged_attention_ragged(
        q, k_cache, v_cache, block_tables, q_start, q_len, seq_lens,
        block_size=block_size, max_q_len=1, q_tile=1, kv_tile=kv_tile,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )
