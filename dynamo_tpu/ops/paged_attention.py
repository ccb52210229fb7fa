"""Pallas TPU ragged paged-attention kernel.

ONE kernel serves every attention shape the engine dispatches against the
paged KV cache — following the *Ragged Paged Attention* design (PAPERS.md):

- decode rows (``q_len == 1``) — the serving hot loop,
- spec-verify windows (``q_len == k+1``, ``engine.model.raw_spec_window_fn``),
- prefill chunks (``q_len`` up to the chunk budget),

all mixed in one launch.  Queries are packed along a single flat axis; each
row ``r`` owns the slots ``[q_start[r], q_start[r+1])`` and fills the first
``q_len[r]`` of them.  The per-row ``(q_start, q_len, ctx_len)`` metadata and
the block tables ride ``PrefetchScalarGridSpec`` scalar prefetch into SMEM.

The work follows the tokens attended, not the table's width.  The grid is
``(rows, q tiles)``; K and V stay in HBM (``pl.ANY``) and each grid step
walks its own row's context in a ``lax.fori_loop`` whose trip count is a
run-time scalar: ``cdiv(frontier, kv_tile)``, where ``frontier`` is
``ctx_len[r]`` for a decode row and the q tile's causal frontier for a spec
or prefill row.  One iteration covers ``kv_tile`` key positions — by default
several pages (``default_kv_tile``) — fetched by one ``make_async_copy`` per
table entry (all KV heads of a page are one contiguous ``[KV, bs, hd]``
transfer) into a two-slot VMEM scratch: while a tile is computed the next
one is in flight, and the last iteration of a grid step starts the first
tile of the *next* grid step, so rows a few tiles long (the serving median)
do not each pay a DMA latency.  A dead row or tile runs zero iterations.
Nothing static depends on a context length or on how wide the table is.

History: up to PR 24 the table's width was a grid axis and the pipeline's
``BlockSpec`` index maps did the paged gather; every row then cost
``max_blocks_per_seq`` grid steps a layer whatever it attended, and the
ledger read the kernel at 1-2 % of its memory roofline (104.7 ms of a
135.6 ms decode step at 48 rows x ~266 tokens; ledger and PERF.md, PR 24).

Flash-style online softmax keeps nothing materialised; per-row causal
masking makes query ``i`` of row ``r`` (absolute position
``ctx_len - q_len + i``) see exactly the keys at positions ``<= that``.

Trash-block contract (physical block 0): the scheduler never allocates
block 0 and scatters every padding write into it, so its contents are
arbitrary.  The kernel guarantees that rows with ``q_len == 0`` (freshly
reset seats, padding rows) and key slots at positions ``>= ctx_len``
(partial last blocks, stale table tails, the pages that round a walk up to
a whole tile) contribute *exactly zero* and can never NaN-poison the online
softmax: masked K/V is zeroed before the MXU, masked scores go to ``-inf``
behind a finite-max guard, and a zero softmax denominator divides as 1 —
dead rows emit exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_LANES = 128
# keys one step of the walk aims for: two lane-width score tiles, the
# winner or the tie of every chip reading of 128 against 256 (PERF.md, PR 30)
_STEP_KEYS = 256
# most of the scoped limit one step's K and V may take: two DMA slots in
# the page dtype plus the float32 working copies the maths makes of them
_STEP_BYTES = VMEM_LIMIT_BYTES // 8


def default_kv_tile(block_size: int, kv_heads: int, head_dim: int,
                    page_dtype) -> int:
    """Key positions one step of the KV walk covers when none is asked for.

    Whole pages up to ``_STEP_KEYS`` keys, halved while the step's K and V
    (two slots each in ``page_dtype``, float32 working copies of one slot,
    heads as wide as the lanes they fill) would not fit ``_STEP_BYTES``;
    never less than one page.  ``kv_heads`` is what the launch sees — the
    shard's share under ``shard_map``.
    """
    pages = max(1, _STEP_KEYS // block_size)
    lanes = -(-head_dim // _LANES) * _LANES
    per_key = kv_heads * lanes * (4 * jnp.dtype(page_dtype).itemsize + 4 * 4)
    while pages > 1 and pages * block_size * per_key > _STEP_BYTES:
        pages //= 2
    return pages * block_size


def _scale_rows(scale: jax.Array) -> jax.Array:
    """``[num_blocks, KV, bs]`` scales as ``[num_blocks, 1, L]`` rows, lane
    ``kv * bs + pos``, ``L`` padded to whole 128-lane tiles: the one shape
    of so narrow a plane that a DMA can take a page of (Mosaic slices an
    HBM operand only in whole tiles of its minor dimensions)."""
    nb, kv, bs = scale.shape
    rows = scale.reshape(nb, 1, kv * bs)
    return jnp.pad(rows, [(0, 0), (0, 0), (0, -(kv * bs) % _LANES)])


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
    k_hbm,         # [num_blocks, KV, bs, hd] in HBM
    v_hbm,         # [num_blocks, KV, bs, hd] in HBM
    # quantized kv_dtype adds two scale planes here: ks_hbm/vs_hbm
    # [num_blocks, 1, L] f32 in HBM (a page's [KV, bs] scales as one
    # lane-dense row, see _scale_rows), and their two VMEM slots after
    # v_buf (see *rest unpacking below)
    *rest,
    block_size: int,
    kv_tile: int,
    q_tile: int,
    scale: float,
    quantized: bool = False,
    window: int = 0,
    v_width: int = 0,
):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
         sem, slot_ref, m_ref, l_ref, acc_ref) = rest
    else:
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
        o_ref, k_buf, v_buf, sem, slot_ref, m_ref, l_ref, acc_ref = rest
    r = pl.program_id(0)
    t = pl.program_id(1)
    num_r = pl.num_programs(0)
    num_t = pl.num_programs(1)
    W = tables_ref.shape[1]
    bs = block_size
    # one tile of the walk = `pieces` DMAs of `piece` key positions each:
    # whole pages when kv_tile is a multiple of the block size, one slice
    # of a page when it sub-splits a block
    pieces = max(1, kv_tile // bs)
    piece = min(kv_tile, bs)

    def walk(r_, t_):
        """(tiles this grid step walks, the first of them, its q tile,
        q_len, ctx_len).  The walk is tiles ``[lo, lo + n)``: ``lo`` is 0
        without a window, else the tile of the lowest key the tile's first
        query may see."""
        q_len = q_len_ref[r_]
        ctx_len = ctx_len_ref[r_]
        alloc, t_eff = _row_tile(t_, q_start_ref, r_, q_tile)
        # the step owns an output tile with at least one valid query
        live = (t_ < alloc) & (t_eff * q_tile < q_len)
        # highest key position any query of this tile may see, plus one
        last_q = jnp.minimum((t_eff + 1) * q_tile, q_len) - 1
        frontier = ctx_len - q_len + last_q + 1
        n = jnp.where(live, (frontier + kv_tile - 1) // kv_tile, 0)
        if not window:
            return jnp.maximum(n, 0), 0, alloc, t_eff, q_len, ctx_len
        first_key = ctx_len - q_len + t_eff * q_tile - (window - 1)
        lo = jnp.where(live, jnp.maximum(first_key, 0) // kv_tile, 0)
        return jnp.maximum(n - lo, 0), lo, alloc, t_eff, q_len, ctx_len

    def fetch(r_, i, slot, *, wait=False):
        """Start (or wait for) the DMAs of tile ``i`` of row ``r_`` into
        ``slot``: per piece one copy of K, one of V and, quantized, one row
        of each scale plane, all on the slot's semaphore.  A loop, not an
        unrolled list: the traced kernel stays small whatever the tile.  A
        wait needs the destination and the semaphore only, so it reads no
        table."""
        def piece_copies(j, carry):
            if wait:
                blk, off = 0, 0
            else:
                pos = i * kv_tile + j * piece
                # columns past the table (a tile rounds the walk up) reread
                # its last entry: those positions are >= ctx_len, masked
                blk = tables_ref[r_, jnp.minimum(pos // bs, W - 1)]
                off = pos % bs
            planes = [(k_hbm, k_buf, piece < bs)]
            if not v_width:          # a latent page is its own value
                planes.append((v_hbm, v_buf, piece < bs))
            if quantized:
                # a page's scales are one row, whole whatever the piece
                planes += [(ks_hbm, ks_buf, False), (vs_hbm, vs_buf, False)]
            for hbm, buf, sliced in planes:
                src = hbm.at[blk]
                if sliced:
                    src = src.at[:, pl.ds(off, piece)]
                dma = pltpu.make_async_copy(
                    src, buf.at[slot, j], sem.at[slot])
                dma.wait() if wait else dma.start()
            return carry

        jax.lax.fori_loop(0, pieces, piece_copies, 0)

    n, lo, alloc, t_eff, q_len, ctx_len = walk(r, t)
    end = lo + n if window else n    # one past the walk's last tile
    first = (r == 0) & (t == 0)
    # the slot this step's first tile is (being) copied into
    slot0 = jnp.where(first, 0, slot_ref[0])

    last_t = t + 1 == num_t
    r_next = jnp.where(last_t, r + 1, r)
    t_next = jnp.where(last_t, 0, t + 1)
    has_next = r_next < num_r
    r_next = jnp.minimum(r_next, num_r - 1)
    n_next, lo_next = walk(r_next, t_next)[:2]
    n_next = jnp.where(has_next, n_next, 0)

    @pl.when(first & (n > 0))
    def _start_own():
        # only the launch's first step fetches its own first tile; every
        # later step finds it started by the step before
        fetch(r, lo, slot0)

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(i, carry):
        # ``i`` is the tile's place in the context: tiles lo .. lo + n - 1
        slot = (slot0 + i - lo) % 2 if window else (slot0 + i) % 2

        # what follows this tile: the row's next one, or — from a step's
        # last iteration — the first tile of the next grid step
        more = i + 1 < end

        @pl.when(more | (n_next > 0))
        def _start_following():
            fetch(jnp.where(more, r, r_next),
                  jnp.where(more, i + 1, lo_next), 1 - slot)

        fetch(r, i, slot, wait=True)

        KV, TQ, G, hd = q_ref.shape
        # a latent page is attended by every head at once (G = all of
        # them): its products run in the page's own dtype with float32
        # accumulation, or the float32 passes of H x 640 x 256 a tile are
        # the kernel's time (2.75 ms a step at 128 rows: PERF.md, PR 34)
        work = q_ref.dtype if v_width else jnp.float32
        q = q_ref[...].astype(work).reshape(KV, TQ * G, hd)
        ks, vs = [], []
        for j in range(pieces):
            kj = k_buf[slot, j].astype(work)             # [KV, piece, hd]
            vj = kj if v_width else v_buf[slot, j].astype(jnp.float32)
            if quantized:
                # quantized pages: dequantize with the per-(slot, head)
                # scales BEFORE the trash-slot zeroing below, so arbitrary
                # bits in the trash block's scale rows (NaN included) are
                # wiped by the same jnp.where that wipes the page payload.
                # The row holds scale (kv, pos) at lane kv*bs + pos: pick
                # each piece slot's own lane and sum the rest away as zeros.
                pos0 = (i * kv_tile + j * piece) % bs if piece < bs else 0
                own = jax.lax.broadcasted_iota(
                    jnp.int32, (KV, piece, 1), 0) * bs + pos0 + \
                    jax.lax.broadcasted_iota(jnp.int32, (KV, piece, 1), 1)
                lane = jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, ks_buf.shape[-1]), 2)
                mine = lane == own                       # [KV, piece, L]
                kj = kj * jnp.sum(
                    jnp.where(mine, ks_buf[slot, j][None], 0.0),
                    axis=-1, keepdims=True)
                vj = vj * jnp.sum(
                    jnp.where(mine, vs_buf[slot, j][None], 0.0),
                    axis=-1, keepdims=True)
            ks.append(kj)
            vs.append(vj)
        k = ks[0] if pieces == 1 else jnp.concatenate(ks, axis=1)
        v = vs[0] if pieces == 1 else jnp.concatenate(vs, axis=1)
        # keys at positions >= ctx_len live in the trash block / a stale
        # table tail — their bits are arbitrary (NaN included).  Zero them
        # BEFORE the MXU: -inf score masking alone still lets NaN·0 leak
        # through the p@v product.
        kpos = i * kv_tile + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_tile, 1), dimension=1
        )                                                # [1, kv_tile, 1]
        kvalid = kpos < ctx_len
        if v_width:
            k = jnp.where(kvalid, k, jnp.zeros((), k.dtype))
            v = k[..., :v_width]
        else:
            k = jnp.where(kvalid, k, 0.0)
            v = jnp.where(kvalid, v, 0.0)

        # batched over KV heads: [KV, TQ*G, hd] x [KV, kv_tile, hd] -> s
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [KV, TQ*G, kv_tile]

        # per-query causal mask: flat row j is query t_eff*TQ + j//G at
        # absolute position ctx_len - q_len + that
        qi = t_eff * q_tile + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1
        ) // G
        spos = i * kv_tile + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=2
        )
        valid = (qi < q_len) & (spos <= ctx_len - q_len + qi)
        if window:
            valid = valid & (spos > ctx_len - q_len + qi - window)
        s = jnp.where(valid, s, -jnp.inf)

        m_prev = m_ref[...]                              # [KV, TQ*G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # m_new can only be -inf while no valid key has been seen; the
        # guard keeps exp() finite for fully-masked query rows.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe,
                                  -jnp.inf))             # [KV, TQ*G, 1]
        p = jnp.exp(s - m_safe)                          # [KV, TQ*G, kv_tile]
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype) if v_width else p, v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # [KV, TQ*G, hd]
        return carry

    jax.lax.fori_loop(lo, end, tile, 0)

    @pl.when((n == 0) & (n_next > 0))
    def _hand_on():
        # a step that walks nothing still starts its successor's first tile
        fetch(r_next, lo_next, slot0)

    slot_ref[0] = (slot0 + n) % 2

    @pl.when((t < alloc) & (t == t_eff))
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
                     "interpret", "window", "v_width", "scale"),
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
    window: int = 0,
    v_width: int = 0,
    scale: float = 0.0,
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

    ``(q_tile, kv_tile)`` change the accumulation order, never the
    contract (``engine.attention_parity`` holds each to an order-exact
    reference; the engine runs the defaults): ``q_tile`` sets the output
    tile height, ``kv_tile`` the key positions one step of a row's KV walk
    covers.  A multiple of ``block_size`` moves that many whole pages a
    step (one DMA per table entry, paged tables being non-contiguous); a
    divisor of it walks each page in ``block_size // kv_tile`` slices.
    ``0`` means the
    default: ``min(max_q_len, 128)`` / ``default_kv_tile`` of the shapes
    this launch sees.  The walk's length is ``cdiv(frontier, kv_tile)``
    read at run time, so neither a context nor the table's width ``W`` is
    part of the compiled program's cost; table columns past a row's
    frontier are never read.

    Quantized KV (``EngineConfig.kv_dtype`` int8/fp8): pass the per-(slot,
    head) float32 scale caches as ``k_scale``/``v_scale`` — the kernel
    dequantizes each K/V page inside the launch (one multiply before the
    MXU), with the scale planes fetched by two more DMAs per table entry
    into slots of their own (pages past a context bring arbitrary scales;
    the in-kernel zeroing wipes them along with the payload).  ``None``
    (the default) traces the exact unquantized kernel.

    ``window > 0`` (a sliding-window layer): query ``i`` sees keys ``j``
    with ``0 <= i - j < window`` only, and a q tile's walk begins at the
    KV tile that holds the lowest key its first query sees instead of at
    tile 0: pages wholly behind the window are never fetched.  ``0`` traces
    the kernel without any of it.

    ``v_width > 0`` (a latent cache, MLA's absorbed decode): a page holds
    one vector a token that is key and value at once; the values are its
    first ``v_width`` dims, ``v_cache`` is not read (pass the same plane),
    and the result is ``[Tq, H, v_width]``.  The page is taken whole, so its
    width need not fill whole lane tiles and nothing is padded.  ``scale``
    (0: ``hd ** -0.5``) multiplies the scores.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    if v_width and quantized:
        raise ValueError("a latent page has no quantized form")
    hd_model = q.shape[-1]
    if not interpret and hd_model % _LANES and not v_width:
        # Mosaic slices an HBM operand only in whole 128-lane tiles: a
        # narrower head is zero-padded to the next one (zeros add nothing
        # to a score or an output) in the same copy that already hands
        # XLA's layout of such a cache over to the kernel's
        pad = [(0, 0)] * 3 + [(0, -hd_model % _LANES)]
        q = jnp.pad(q, pad[1:])
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    Tq, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    R = block_tables.shape[0]
    bs = block_size
    if q_tile <= 0:
        q_tile = min(max_q_len, 128) if max_q_len % min(max_q_len, 128) == 0 \
            else max_q_len
    if max_q_len % q_tile or Tq % q_tile:
        raise ValueError(
            f"q_tile {q_tile} must divide max_q_len {max_q_len} and Tq {Tq}"
        )
    if kv_tile <= 0:
        kv_tile = default_kv_tile(bs, KV, hd, k_cache.dtype)
    if bs % kv_tile and kv_tile % bs:
        raise ValueError(
            f"kv_tile {kv_tile} must divide block_size {bs} or be a "
            f"multiple of it"
        )
    num_t = max_q_len // q_tile
    pieces = max(1, kv_tile // bs)
    piece = min(kv_tile, bs)

    # head-packed flat layout: [KV, Tq, G, hd] so a q tile is one
    # contiguous (KV, TQ, G, hd) block
    q4 = q.reshape(Tq, KV, G, hd).transpose(1, 0, 2, 3)

    def q_map(r, t, q_start, q_len, ctx_len, tables):
        _, t_eff = _row_tile(t, q_start, r, q_tile)
        return (0, q_start[r] // q_tile + t_eff, 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((KV, q_tile, G, hd), q_map), hbm, hbm]
    operands = [q4, k_cache, v_cache]
    hd_out = v_width or hd
    scratch = [
        pltpu.VMEM((2, pieces, KV, piece, hd), k_cache.dtype),
        # unread where the K page is the value too
        pltpu.VMEM((2, pieces, KV, piece, hd) if not v_width
                   else (2, 1, KV, 8, _LANES), v_cache.dtype),
    ]
    if quantized:
        ks_rows, vs_rows = _scale_rows(k_scale), _scale_rows(v_scale)
        in_specs += [hbm, hbm]
        operands += [ks_rows, vs_rows]
        scratch += [
            pltpu.VMEM((2, pieces) + rows.shape[1:], rows.dtype)
            for rows in (ks_rows, vs_rows)
        ]
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),      # one per slot, all its copies
        pltpu.SMEM((1,), jnp.int32),        # slot of this step's first tile
        pltpu.VMEM((KV, q_tile * G, 1), jnp.float32),
        pltpu.VMEM((KV, q_tile * G, 1), jnp.float32),
        pltpu.VMEM((KV, q_tile * G, hd_out), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R, num_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((KV, q_tile, G, hd_out), q_map),
        scratch_shapes=scratch,
    )

    kernel = functools.partial(
        _ragged_kernel, block_size=bs, kv_tile=kv_tile, q_tile=q_tile,
        scale=scale or 1.0 / (hd_model ** 0.5), quantized=quantized,
        window=window, v_width=v_width,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, Tq, G, hd_out), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # in order: a step's last iteration starts the next step's DMA
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q_start, q_len, ctx_len, block_tables, *operands)
    out = out.transpose(1, 0, 2, 3).reshape(Tq, H, hd_out)
    return out if v_width else out[..., :hd_model]


@functools.partial(
    jax.jit, static_argnames=("block_size", "kv_tile", "interpret", "window",
                              "v_width", "scale")
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
    window: int = 0,
    v_width: int = 0,
    scale: float = 0.0,
) -> jax.Array:
    """Single-token-per-sequence paged attention.  Returns ``[B, H, hd]``.

    The decode face of the ragged kernel: every row is one query slot
    (``q_tile == 1``).  ``seq_lens[b]`` counts the valid context slots for
    row ``b`` *including* the token being decoded; ``seq_lens[b] == 0``
    rows emit exact zeros.  ``k_scale``/``v_scale`` carry quantized-KV
    dequant scales exactly as in :func:`paged_attention_ragged`, and
    ``window`` its lower bound: a row of context ``c`` walks from the tile
    of key ``max(0, c - window)``.
    """
    B = q.shape[0]
    q_start = jnp.arange(B + 1, dtype=jnp.int32)
    q_len = (seq_lens > 0).astype(jnp.int32)
    return paged_attention_ragged(
        q, k_cache, v_cache, block_tables, q_start, q_len, seq_lens,
        block_size=block_size, max_q_len=1, q_tile=1, kv_tile=kv_tile,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
        window=window, v_width=v_width, scale=scale,
    )
