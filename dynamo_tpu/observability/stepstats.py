"""Per-engine-step flight records + windowed live gauges.

The engine appends one :class:`StepRecord` per dispatched unit of device
work (a prefill chunk, an autopilot decode window, a spec verify window)
with ONLY host-known Python ints — batch occupancy, bucketed vs real token
counts, attended-context sums — and stamps the landing time when the
``_BatchingFetcher`` completes the window's (already planned) device_get.
No instrumentation ever touches a device array, so the recorder adds zero
host syncs to the hot path (dynalint-enforced).

:class:`StepStats` aggregates the records into windowed gauges:

* ``mfu`` — goodput model-FLOPs / (elapsed * peak * n_chips), split
  ``mfu_prefill`` / ``mfu_decode`` by step class, plus ``mfu_dispatched``
  counting everything the chip executed (padding included)
* ``goodput_tok_s`` — real tokens landed per second
* ``padding_waste_ratio`` — dispatched FLOPs burnt on bucket padding
* ``wasted_flops_ratio{cause=padding|spec_reject}`` — where the
  non-goodput FLOPs went
* ``host_busy_ratio`` — share of the window the engine-loop task was busy
  (``host_s`` sums), the floor a faster device would uncover
* ``loop_busy_ratio`` — share of the window the worker's whole event loop
  was busy (``loop_busy_s`` sums): the engine-loop task and every stream's
  way out on the one thread they share

The FLOPs accounting uses the shared analytic model
(:mod:`.flops` — attention term included, not just ``2·N·params``).
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass, field
from typing import Deque, Dict, Optional

from ..utils.hotpath import hot_path
from .flops import FlopsModel

JSONL_FLUSH_S = 1.0  # most a JSONL capture waits before reaching the file

# step classes
PREFILL = "prefill"
DECODE = "decode"
SPEC_VERIFY = "spec_verify"


def kv_blocks_walked(contexts, *, kv_tile: int, block_size: int,
                     window: int = 0) -> int:
    """KV pages one decode launch of the paged-attention kernel visits in a
    layer, from host integers (``ops.paged_attention``: a row of context
    ``c`` walks ``cdiv(c, kv_tile)`` tiles and fetches every page of each;
    ``c == 0`` is a dead row and walks nothing).  A ``kv_tile`` below
    ``block_size`` fetches a slice of one page per tile and counts it as
    that page again.  The table's width does not appear.  In a layer with a
    ``window`` the walk begins at the tile of key ``max(0, c - window)``."""
    pages = max(1, kv_tile // block_size)
    return sum(
        (-(-int(c) // kv_tile)
         - (max(0, int(c) - window) // kv_tile if window else 0)) * pages
        for c in contexts)


def kv_pages_written(rows, *, block_size: int) -> int:
    """Cache pages one write of a step program touches in ONE layer and
    plane, from host integers: ``rows`` are ``(start, count)`` of each
    row's contiguous new positions; a row of ``count`` 0 writes nothing.
    It is the number of scatter updates the write carries
    (``engine.model._kv_write``), so what its cost should follow."""
    return sum((int(s) + int(n) - 1) // block_size - int(s) // block_size + 1
               for s, n in rows if n > 0)


def latent_keys_walked(start: int, length: int, tiles, width: int) -> int:
    """Keys ONE latent layer's attention visits for a prefill chunk of
    ``length`` tokens at positions ``start ..`` whose table gathers
    ``width`` keys, from host integers (``ops.latent_chunk_attention``: each
    ``q_tile`` of the chunk with a valid row walks whole ``kv_tile``s up to
    its last position).  With no ``tiles`` (the einsum, which attends all
    it is given) ``width``."""
    if tiles is None:
        return width
    q_tile, kv_tile = tiles
    return sum(-(-(start + min(length, t + q_tile)) // kv_tile) * kv_tile
               for t in range(0, length, q_tile))


@dataclass
class StepRecord:
    """One dispatched unit of device work, host-side metadata only."""

    kind: str                 # prefill | decode | spec_verify
    t_dispatch: float         # monotonic, at enqueue on the step thread
    t_land: float = 0.0       # monotonic, when the window's fetch landed
    bucket: int = 0           # compiled bucket this dispatch padded up to
    rows: int = 0             # padded batch rows the program computes
    live_rows: int = 0        # rows carrying a scheduled sequence
    padded_tokens: int = 0    # tokens the compiled program computes
    real_tokens: int = 0      # tokens backed by real sequence positions
    goodput_tokens: int = 0   # tokens that advanced a sequence (landing)
    context_sum: int = 0      # sum of attended context over real tokens
    # decode records: KV pages the attention launches of this window visit
    # in ONE layer (kv_blocks_walked above over the same row contexts as
    # context_sum, at the tile the window was traced with; the einsum path
    # gathers every column of the table: rows x width). Times block_size
    # over context_sum = how far the walk is from the tokens attended.
    kv_blocks_walked: int = 0
    # every record: cache pages the step's K/V write touches in ONE layer
    # (kv_pages_written above over each row's start and count; a decode
    # window's K steps each write one page a live row, a spec window all
    # k + 1 fed positions of a row at once, as if every draft were fed).
    # Beside rows and real_tokens it says what the write's cost follows.
    kv_pages_written: int = 0
    # the same two for ONE layer with a window (a table's sliding kind; 0
    # where the model has none): pages walked from the window's tile on,
    # and min(context, window) summed over the same rows
    kv_blocks_walked_window: int = 0
    context_sum_window: int = 0
    # a table with linear-attention layers: seats whose state ONE such
    # layer reads and writes in this record (a live row in each of a decode
    # window's K steps, a prefill chunk's one row); with latent (MLA)
    # layers: positions ONE such layer attends, context_sum's count (0
    # where the model has no such layer)
    state_rows: int = 0
    latent_context_sum: int = 0
    # prefill records of a table with latent layers: keys ONE such layer's
    # attention visits for this chunk (latent_keys_walked above: whole key
    # tiles up to the chunk's last position; the einsum attends all it is
    # given) beside the keys its table gathers (width x block_size): their
    # ratio is the share of the padded width that is walked
    latent_keys_walked: int = 0
    latent_keys_gathered: int = 0
    # decode records of a table with routed experts, summed over the
    # window's steps and sparse layers, read from the window's own fetch
    # (model.moe_stats_row): (token, expert) pairs of live rows, those of
    # them whose expert is held here, held experts with at least one token,
    # those whose choice is a zero-compute (identity) expert, which reads no
    # expert's weights (0 where the router has none), the largest token
    # count on one held expert in a layer, and the held pairs behind the
    # first slab of a call whose rows follow the held pairs
    # (parallel.moe.held_rows; 0 where the rows are all the pairs)
    moe_pairs: int = 0
    moe_pairs_held: int = 0
    moe_experts_touched: int = 0
    moe_pairs_zero: int = 0
    moe_load_max: int = 0
    moe_pairs_overflow: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    # host seconds (``time.monotonic()`` differences, no device access).
    # dispatch_s: the dispatch thread inside this record's _dispatch_prefill
    # / _dispatch_decode. host_s, unpack_s are per BATCH and sit on its
    # decode record (its last record when it has none): host_s the
    # engine-loop task's busy time since it handed off the previous batch,
    # unpack_s the fetch thread from the device_get's return to commit.
    # loop_busy_s has host_s's meaning for the whole event loop: seconds
    # the loop ran callbacks (this task's and every stream's) rather than
    # waiting in its selector since the previous handoff, so host_s is a
    # part of it (0 where the loop has no counter: runtime.loop_busy).
    # "Not waiting in its selector" includes waiting for the interpreter
    # lock another thread holds; loop_cpu_s is the loop thread's CPU
    # seconds over the same stretch, the part of loop_busy_s that was work.
    host_s: float = 0.0
    loop_busy_s: float = 0.0
    loop_cpu_s: float = 0.0
    dispatch_s: float = 0.0
    unpack_s: float = 0.0
    # filled by StepStats.commit from the shared FLOPs model
    flops_dispatched: float = 0.0
    flops_real: float = 0.0
    flops_goodput: float = 0.0


@dataclass
class _Window:
    """Running sums over the committed records inside the live window."""

    steps: int = 0
    goodput_tokens: int = 0
    real_tokens: int = 0
    padded_tokens: int = 0
    flops_dispatched: float = 0.0
    flops_goodput: float = 0.0
    flops_padding_waste: float = 0.0
    flops_spec_waste: float = 0.0
    flops_goodput_prefill: float = 0.0
    flops_goodput_decode: float = 0.0
    spec_drafted: int = 0
    spec_accepted: int = 0
    host_s: float = 0.0
    loop_busy_s: float = 0.0

    def add(self, r: StepRecord, sign: int = 1) -> None:
        self.steps += sign
        self.host_s += sign * r.host_s
        self.loop_busy_s += sign * r.loop_busy_s
        self.goodput_tokens += sign * r.goodput_tokens
        self.real_tokens += sign * r.real_tokens
        self.padded_tokens += sign * r.padded_tokens
        self.flops_dispatched += sign * r.flops_dispatched
        self.flops_goodput += sign * r.flops_goodput
        self.flops_padding_waste += sign * (r.flops_dispatched - r.flops_real)
        self.flops_spec_waste += sign * (r.flops_real - r.flops_goodput)
        if r.kind == PREFILL:
            self.flops_goodput_prefill += sign * r.flops_goodput
        else:
            self.flops_goodput_decode += sign * r.flops_goodput
        self.spec_drafted += sign * r.spec_drafted
        self.spec_accepted += sign * r.spec_accepted


class StepStats:
    """Thread-safe windowed aggregator over :class:`StepRecord` commits.

    Commits arrive from the fetch/executor threads; ``snapshot()`` is read
    from the event loop (publisher, spans, bench). The window is a deque
    pruned by landing time, so gauges always describe the last
    ``window_s`` seconds of *landed* device work."""

    def __init__(
        self,
        flops_model: FlopsModel,
        *,
        n_chips: int = 1,
        peak_flops: Optional[float] = None,
        window_s: float = 10.0,
        capacity: int = 8192,
        jsonl_path: str = "",
        clock=time.monotonic,
    ):
        self.flops_model = flops_model
        self.n_chips = max(1, n_chips)
        # None = the platform has no published peak: MFU keys are absent
        self.peak_flops = peak_flops
        self.window_s = window_s
        self.capacity = capacity
        self.jsonl_path = jsonl_path
        self._clock = clock
        self._lock = threading.Lock()
        self._records: Deque[StepRecord] = deque()
        self._win = _Window()
        self._t_start = clock()       # window floor (reset at warmup end)
        self._warmup_done = False
        self._jsonl_fh = None
        self._jsonl_flushed = 0.0   # clock() of the last flush
        # lifetime totals (never pruned) — survive window rollover
        self.total_steps = 0
        self.total_goodput_tokens = 0
        # snapshot cache: span recording reads this per request; recomputing
        # the window sums each time would scale with request rate
        self._snap_cache: Optional[Dict[str, float]] = None
        self._snap_cache_t = 0.0

    # ------------------------------ commit -----------------------------

    @hot_path
    def commit(self, rec: StepRecord) -> None:
        """Finalize one landed record (fetch/executor thread; Python ints
        only — no device access). The padded-shape FLOPs scale the real
        attention term by the padding ratio, a documented lower bound for
        gather-style attention that materialises the full bucket."""
        fm = self.flops_model
        rec.flops_real = fm.step_flops(rec.real_tokens, rec.context_sum)
        if rec.real_tokens > 0:
            padded_ctx = rec.context_sum * rec.padded_tokens / rec.real_tokens
        else:
            padded_ctx = 0.0
        rec.flops_dispatched = fm.step_flops(rec.padded_tokens, padded_ctx)
        goodput_ctx = (rec.context_sum * rec.goodput_tokens
                       / rec.real_tokens if rec.real_tokens else 0.0)
        rec.flops_goodput = fm.step_flops(rec.goodput_tokens, goodput_ctx)
        with self._lock:
            self._records.append(rec)
            self._win.add(rec)
            self.total_steps += 1
            self.total_goodput_tokens += rec.goodput_tokens
            self._snap_cache = None
            self._prune_locked(self._clock())
        if self.jsonl_path:
            self._write_jsonl(rec)

    def _prune_locked(self, now: float) -> None:
        floor = now - self.window_s
        while self._records and (
                self._records[0].t_land < floor
                or len(self._records) > self.capacity):
            self._win.add(self._records.popleft(), sign=-1)

    def _write_jsonl(self, rec: StepRecord) -> None:
        line = json.dumps(asdict(rec), separators=(",", ":"))
        with self._lock:
            if self._jsonl_fh is None:
                self._jsonl_fh = open(self.jsonl_path, "a")
            self._jsonl_fh.write(line + "\n")
            # buffered: a flush a second at most, the rest on close() — a
            # crash loses under a second of records, a traced run keeps
            # the syscall off every landing
            now = self._clock()
            if now - self._jsonl_flushed >= JSONL_FLUSH_S:
                self._jsonl_fh.flush()
                self._jsonl_flushed = now

    def close(self) -> None:
        with self._lock:
            if self._jsonl_fh is not None:
                self._jsonl_fh.close()
                self._jsonl_fh = None

    # ---------------------------- lifecycle ----------------------------

    def mark_warmup_done(self) -> None:
        """Drop everything recorded so far: compiles and cold caches make
        warmup windows unrepresentative, and bench/steady-state gauges
        must describe the measured loop only."""
        with self._lock:
            self._records.clear()
            self._win = _Window()
            self._t_start = self._clock()
            self._warmup_done = True
            self.total_steps = 0
            self.total_goodput_tokens = 0
            self._snap_cache = None

    # ---------------------------- snapshot -----------------------------

    def snapshot(self, max_age_s: float = 0.25) -> Dict[str, float]:
        """Live gauges over the trailing window (cached ``max_age_s``)."""
        now = self._clock()
        with self._lock:
            if (self._snap_cache is not None
                    and now - self._snap_cache_t <= max_age_s):
                return dict(self._snap_cache)
            self._prune_locked(now)
            w = self._win
            # elapsed: window span, floored at the warmup mark so a
            # freshly-reset recorder doesn't divide by ~0
            elapsed = min(self.window_s, max(now - self._t_start, 1e-9))
            dispatched = max(w.flops_dispatched, 0.0)
            snap = {}
            if self.peak_flops:
                denom = elapsed * self.peak_flops * self.n_chips
                snap = {
                    "mfu": w.flops_goodput / denom,
                    "mfu_prefill": w.flops_goodput_prefill / denom,
                    "mfu_decode": w.flops_goodput_decode / denom,
                    "mfu_dispatched": dispatched / denom,
                }
            snap.update({
                "goodput_tok_s": w.goodput_tokens / elapsed,
                "host_busy_ratio": max(w.host_s, 0.0) / elapsed,
                "loop_busy_ratio": max(w.loop_busy_s, 0.0) / elapsed,
                "padding_waste_ratio": (
                    w.flops_padding_waste / dispatched if dispatched else 0.0),
                "spec_reject_waste_ratio": (
                    w.flops_spec_waste / dispatched if dispatched else 0.0),
                "steps_in_window": float(w.steps),
                "window_s": elapsed,
                "total_steps": float(self.total_steps),
                "total_goodput_tokens": float(self.total_goodput_tokens),
                "spec_drafted": float(w.spec_drafted),
                "spec_accepted": float(w.spec_accepted),
            })
            self._snap_cache = snap
            self._snap_cache_t = now
            return dict(snap)
