"""The async inference engine: scheduler + jitted steps + token streaming.

Role-equivalent to vLLM's ``AsyncLLM`` in the reference's workers (ref:
components/backends/vllm/src/dynamo/vllm/main.py:97), built TPU-native: an
asyncio step loop plans batches with the continuous-batching scheduler, runs
the jitted unified prefill/decode step on device (dispatched from a dedicated
executor thread so the event loop never blocks on XLA), and streams sampled
tokens into per-request queues. KV events and ForwardPassMetrics-equivalent
stats are surfaced in-process — the seam the reference covers with ZMQ
(publisher.rs:223) collapses here because the engine is ours.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (
    Any, AsyncIterator, Callable, Deque, Dict, List, Optional, Tuple,
)

import jax
import numpy as np

from .. import tracing
from ..observability import compilewatch, profiling
from ..observability import flops as obs_flops
from ..parallel import layout
from ..parallel.moe import GMM_TILES_TRACED, MOE_STATS
from ..observability.flops import FlopsModel
from ..observability.stepstats import (
    DECODE, PREFILL, SPEC_VERIFY, StepRecord, StepStats, kv_blocks_walked,
    kv_pages_written, latent_keys_walked,
)
from ..runtime import faults, loop_busy
from ..runtime.context import Context
from ..runtime.engine import AsyncEngine
from ..utils.config import env_flag, env_float, env_str
from ..utils.hotpath import hot_path
from ..utils.logging import get_logger
from .config import EngineConfig, ModelConfig
from . import model as model_lib
from . import quant
from .scheduler import (
    KvEvent, PrefillChunk, SchedSeq, Scheduler, SchedulerStats, SeqStatus,
)

log = get_logger("engine")


@dataclass
class Request:
    """One generation request (preprocessed: token ids in)."""

    request_id: str
    token_ids: List[int]
    max_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None
    eos_token_ids: Tuple[int, ...] = ()
    ignore_eos: bool = False
    # multimodal EPD: precomputed vision embeddings spliced over
    # placeholder prompt positions, plus the content-addressed ids used
    # for KV block hashing (never as model inputs) so the prefix cache
    # can't serve one image's KV for another
    mm_positions: Optional[List[int]] = None
    mm_embeddings: Optional[np.ndarray] = None   # [len(mm_positions), D]
    mm_hash_token_ids: Optional[List[int]] = None


# Engine-loop phases, as ``jax.profiler.TraceAnnotation`` names on the
# thread that runs each: a /debug/profile capture shows them on the
# profiler's clock beside the device ops (no-ops while nothing captures).
PHASES = (
    "engine.schedule", "engine.postprocess",      # event loop, busy
    "engine.wait_land", "engine.wait_idle",       # event loop, waiting
    "engine.dispatch",                            # dispatch thread
    "engine.dispatch.prefill", "engine.dispatch.decode",
    "engine.fetch", "engine.unpack",              # fetch thread
    # event loop, every stream's task: pack + write of one data frame
    # (the ingress server's, which must not import JAX: serve_engine hands
    # it ``stream_out_phase``)
    "worker.stream_out",
)
_phase = jax.profiler.TraceAnnotation


def stream_out_phase():
    return _phase("worker.stream_out")


class _LoopClock:
    """Where the engine-loop task's wall time goes: seconds spent waiting
    (``land``: for a window's results, ``idle``: for work, ``executor``:
    for the dispatch thread) and, by difference, busy. ``handoff()`` closes
    the account of one batch: its busy seconds ride the batch to its step
    record as ``host_s``, its waits are added to ``wait_s``. So at any
    moment ``sum(host_s handed off) + sum(wait_s.values())`` equals
    ``t_handoff - t_start``.

    The task's waits are when every OTHER task of the loop runs (each
    stream's queue, pack, write and drain), so beside its own busy seconds a
    handoff reads what the whole loop was busy since the last one, from the
    loop's ``runtime.loop_busy`` counter: ``loop_busy_s``, of which
    ``host_s`` is a part (0 where the loop has no counter). Busy there is
    "not in a blocking select", so it holds the loop thread's waits for the
    interpreter lock too (the dispatch and fetch threads take it every
    step); ``loop_cpu_s``, the thread's CPU seconds over the same stretch,
    is the part that was work."""

    def __init__(self, loop_counter=None):
        now = self.t_start = self.t_handoff = time.monotonic()
        self.wait_s = {"land": 0.0, "idle": 0.0, "executor": 0.0}
        self._open = dict(self.wait_s)  # waits since the last handoff
        self._loop_counter = loop_counter
        self._loop_busy_at = loop_counter.busy_s(now) if loop_counter else 0.0
        self._cpu_at = time.thread_time()   # made and read on the loop thread

    @contextlib.contextmanager
    def waiting(self, what: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self._open[what] += time.monotonic() - t0

    def handoff(self) -> Tuple[float, float, float]:
        """(``host_s``, ``loop_busy_s``, ``loop_cpu_s``) since the previous
        handoff."""
        now = time.monotonic()
        busy = now - self.t_handoff - sum(self._open.values())
        for what, s in self._open.items():
            self.wait_s[what] += s
            self._open[what] = 0.0
        self.t_handoff = now
        loop_busy = 0.0
        if self._loop_counter is not None:
            # at the instant host_s was closed: a thread switch between two
            # reads of the clock would make this handoff's stretch longer
            # and the next one's shorter than its host_s
            total = self._loop_counter.busy_s(now)
            loop_busy = total - self._loop_busy_at
            self._loop_busy_at = total
        cpu = time.thread_time()
        loop_cpu, self._cpu_at = cpu - self._cpu_at, cpu
        return max(busy, 0.0), max(loop_busy, 0.0), loop_cpu


@contextlib.contextmanager
def _dispatch_phase(name: str, obs_out):
    """One ``_dispatch_prefill`` / ``_dispatch_decode`` call (dispatch
    thread): a phase carrying the dispatch's ``bucket`` and ``rows``, and
    its seconds as ``dispatch_s`` on the step record the call appends to
    ``obs_out`` (None = recorder off: the bare phase)."""
    n = len(obs_out) if obs_out is not None else 0
    t0 = time.monotonic()
    with _phase(name) as phase:
        yield
        if obs_out is not None and len(obs_out) > n:
            rec = obs_out[-1]
            rec.dispatch_s = time.monotonic() - t0
            phase.set_metadata(bucket=rec.bucket, rows=rec.live_rows)


class _BatchingFetcher:
    """One thread draining a queue of (batch, handles, future), one
    ``jax.device_get`` per WINDOW, with the D2H copy started
    asynchronously at submit time. A cold get is a host sync (~64 ms+
    when measured on an earlier transport; re-measured by chip_smoke.py,
    see CHANGES); ``copy_to_host_async`` at dispatch overlaps the
    transfer with compute, so by the time the fetch thread reaches a
    window its bytes are (usually) already host-side and the get is
    cheap. Fetching per window — instead of grouping the whole backlog
    into one get — is what keeps inter-token latency real: each window's
    tokens flush to the SSE streams as that window lands, not in one
    burst when the backlog drains."""

    def __init__(self, unpack, on_sync=None):
        import queue as _queue

        self._q: Any = _queue.Queue()
        self._unpack = unpack
        self._on_sync = on_sync   # () -> None, counts host syncs
        self._thread = None

    def ensure_started(self) -> None:
        if self._thread is None:
            import threading

            self._thread = threading.Thread(
                target=self._run, daemon=True, name="tpu-fetch"
            )
            self._thread.start()

    def submit(self, loop, batch, handles):
        fut = loop.create_future()
        # kick off the device→host transfer now, while the next window
        # computes; the fetch thread's device_get then mostly finds the
        # bytes already resident
        for arr in self._flat(handles):
            try:
                arr.copy_to_host_async()
            except Exception:
                pass  # best-effort (some backends/arrays don't support it)
        self._q.put((loop, batch, handles, fut))
        return fut

    def stop(self) -> None:
        if self._thread is not None:
            self._q.put(None)

    @staticmethod
    def _flat(handles) -> List[Any]:
        ph, dh = handles
        return list(ph) + ([dh[0]] if dh is not None else [])

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            loop, batch, handles, fut = item
            flat = self._flat(handles)
            try:
                # THE designed host sync: one device_get per window, on the
                # fetcher thread, off the dispatch loop
                with _phase("engine.fetch"):
                    got = jax.device_get(flat) if flat else []  # dynalint: disable=DT102
                if flat and self._on_sync is not None:
                    self._on_sync()
                res, exc = self._unpack(batch, handles, got), None
            except Exception as e:  # donated-buffer poison, backend death
                res, exc = None, e
            try:
                loop.call_soon_threadsafe(_fut_set, fut, res, exc)
            except RuntimeError:
                # the loop closed under us (engine torn down mid-flight);
                # keep draining so the remaining futures get resolved
                pass


def _fut_set(fut, res, exc) -> None:
    if fut.cancelled():
        return
    if exc is not None:
        fut.set_exception(exc)
    else:
        fut.set_result(res)


@dataclass
class StepOutput:
    """One streamed generation step for a request."""

    request_id: str
    token_id: int
    index: int                 # 0-based output token index
    finished: bool = False
    finish_reason: Optional[str] = None
    num_prompt_tokens: int = 0
    cached_prompt_tokens: int = 0
    # monotonic stamp of the fetch that brought this token to the host (the
    # fetch thread's, right after its device_get returned); None where no
    # fetch did (the mocker, a remotely sampled first token). Stays in the
    # process: the stream's task reads how long the token took to reach it.
    t_land: Optional[float] = None


def _seed31(seed) -> int:
    """Map an arbitrary user seed into the int32-safe [0, 2^31) range the
    device arrays carry (-1 = unseeded). u64-scale seeds are valid on the
    wire (ref SamplingOptions); an unmasked one would OverflowError inside
    the step loop and kill every in-flight sequence."""
    return -1 if seed is None else int(seed) & 0x7FFFFFFF


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _pow2_bucket(n: int, cap: Optional[int] = None) -> int:
    b = 1
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


class EngineCore(AsyncEngine):
    """Device-agnostic continuous-batching engine core.

    Owns the scheduler, the asyncio step loop, per-request streaming queues,
    and KV-event/stat surfacing. Subclasses provide the actual batch
    execution: :class:`InferenceEngine` dispatches jitted JAX steps; the
    mocker (``dynamo_tpu.mocker``) simulates step timing without a device
    (ref: lib/llm/src/mocker/engine.rs:48 — same split, the reference's
    mocker also reuses the real scheduler semantics).

    ``generate`` accepts wire-format dict requests (token_ids + sampling
    options) and yields wire-format dict outputs, so it can be served directly
    by ``Endpoint.serve_endpoint``.
    """

    def __init__(self, engine_config: EngineConfig):
        self.config = engine_config
        self.scheduler = Scheduler(engine_config, on_event=self._on_kv_event)
        self._queues: Dict[str, asyncio.Queue] = {}
        self._seqs: Dict[str, SchedSeq] = {}
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._stopped = False
        self._ids = itertools.count(1)
        self.kv_event_sink: Optional[Callable[[dict], None]] = None
        self._pending_events: List[dict] = []
        # disagg reservation epochs: seq_id -> epoch while the reservation
        # is live (reserve_sequence .. resume_prefilled/cancel_reservation).
        # Transfers stamped with an older epoch are rejected before write.
        self._kv_epoch = itertools.count(1)
        self._kv_reservations: Dict[str, int] = {}
        self.kvbm = None  # multi-tier block manager (attach_kvbm)
        self.prefix = None  # radix prefix cache (attach_prefix_cache)
        # run-ahead depth: how many scheduled windows may be in flight
        # before the loop waits for a landing. 1 = classic synchronous
        # schedule→execute→postprocess. The JAX engine raises this (device
        # dispatch is async; a host sync must stay off the dispatch path).
        self.pipeline_depth = 1
        # counters
        self.num_generated_tokens = 0
        self.num_steps = 0
        # host syncs (device_get round-trips) — with speculative decoding
        # the headline efficiency metric is tokens landed per sync
        self.num_fetch_syncs = 0
        # SpecDecodeStats when spec decode is active (InferenceEngine sets
        # it); published worker → aggregator and stamped on decode spans
        self.spec_stats = None
        # flight recorder (observability.StepStats) when enabled;
        # InferenceEngine builds it, the mocker leaves it None
        self.obs = None
        # the loop task's busy/wait account (reset when the loop starts)
        self.loop_clock = _LoopClock()
        # -- stall watchdog state (engine_config.stall_timeout_s > 0) --
        # per-seq recovery attempts; a seq over stall_seq_retries is failed
        # instead of requeued so one poisoned prompt can't loop forever
        self._stall_retries: Dict[str, int] = {}
        self._stall_streak = 0       # consecutive stalled landings
        self.num_stalls = 0
        self.stall_dead = False      # streak hit stall_dead_threshold
        # quarantined (kind, bucket) shape classes: dispatch planning routes
        # around them (next bucket up / einsum impl) after a stall
        self._shape_quarantine: set = set()
        self._window_seq = itertools.count(1)  # fault key for engine.stall
        # -- HBM-pressure ladder state (pressure_*_threshold > 0) --
        self.pressure_level = 0          # 0 idle .. 3 shedding
        self.pressure_shedding = False   # rung 3: submit() rejects
        self._pressure_spec_paused = False  # rung 2: spec decode paused
        self._pressure_spec_saved = None    # spec_plan_window to restore
        self._pressure_spill_cool = 0    # min ticks between rung-1 spills
        self.num_pressure_spills = 0
        self.num_pressure_shed = 0
        self.pressure_peak = 0       # highest rung reached this lifetime

    # ------------------------- lifecycle -------------------------------

    async def start(self) -> None:
        if self._loop_task is None:
            self._loop_task = asyncio.create_task(self._run_loop())

    async def stop(self) -> None:
        self._stopped = True
        self._wake.set()
        if self._loop_task is not None:
            await self._loop_task
            self._loop_task = None
        # fail everything still queued/running so no submit() consumer hangs
        for seq in list(self._seqs.values()):
            if seq.status != SeqStatus.FINISHED:
                self.scheduler.abort(seq, "shutdown")
                self._emit_finish(seq, "shutdown")
        self._shutdown_executor()

    def _shutdown_executor(self) -> None:
        pass

    @property
    def stats(self) -> SchedulerStats:
        return self.scheduler.stats

    def clear_kv_blocks(self) -> None:
        """Drop the prefix cache (ref: http clear_kv_blocks endpoint)."""
        self.scheduler.pool.clear()

    def attach_prefix_cache(self, config=None, worker_id: int = 0,
                            plane=None):
        """Enable the radix-tree prefix index on this engine. Works with
        or without a KVBM (index-only mode still gives the scheduler-hit
        cross-check accounting); attach AFTER ``attach_kvbm`` so tier
        transitions (offload/G4/drop) are hooked too."""
        from ..prefix.manager import PrefixCacheManager

        self.prefix = PrefixCacheManager(
            self, kvbm=self.kvbm, config=config, worker_id=worker_id,
            plane=plane,
        )
        self.scheduler.on_prefix_match = self.prefix.on_scheduler_match
        return self.prefix

    # ------------------------- submission ------------------------------

    async def submit(self, request: Request) -> AsyncIterator[StepOutput]:
        """Submit a request; yields StepOutputs as tokens are generated."""
        await self.start()
        if self.pressure_shedding:
            # the loop only ticks the ladder while seats are live; if the
            # pool drained since the last pass, re-evaluate here so an idle
            # engine doesn't shed forever on a stale flag
            self._pressure_tick()
        if self.pressure_shedding:
            # rung 3 of the HBM-pressure ladder: refuse new admissions
            # while resident seats drain; the router retries elsewhere
            self.num_pressure_shed += 1
            raise RuntimeError(
                "admission shed: HBM pressure over pressure_shed_threshold"
            )
        if self.stall_dead:
            raise RuntimeError(
                "engine declared dead after repeated dispatch stalls"
            )
        if not request.token_ids:
            raise ValueError("empty prompt")
        if len(request.token_ids) >= self.config.max_model_len:
            raise ValueError(
                f"prompt length {len(request.token_ids)} exceeds "
                f"max_model_len {self.config.max_model_len}"
            )
        if request.mm_positions:
            # admission-time rejection fails only THIS request; a raise in
            # the step would abort every co-scheduled request (and, for
            # multi-host, after parts of the batch reached followers)
            if getattr(self, "step_sink", None) is not None:
                raise ValueError(
                    "multimodal prefill is not supported in multi-host "
                    "step-replication mode"
                )
            if getattr(self, "pp", 0) > 1:
                raise ValueError(
                    "multimodal prefill unsupported on a pipeline-parallel "
                    "engine"
                )
            model_cfg = getattr(self, "model_config", None)
            if (model_cfg is not None and request.mm_embeddings is not None
                    and np.asarray(request.mm_embeddings).shape[-1]
                    != model_cfg.hidden_size):
                raise ValueError(
                    f"mm embedding width "
                    f"{np.asarray(request.mm_embeddings).shape[-1]} != "
                    f"model hidden size {model_cfg.hidden_size} — is the "
                    f"encode worker's --model-dim wrong?"
                )
        seq = SchedSeq(
            seq_id=request.request_id or f"seq-{next(self._ids)}",
            prompt_ids=list(request.token_ids),
            max_tokens=max(1, request.max_tokens),
            eos_token_ids=(frozenset() if request.ignore_eos
                           else frozenset(request.eos_token_ids)),
            temperature=request.temperature,
            top_k=request.top_k,
            top_p=request.top_p,
            seed=_seed31(request.seed),
            mm_positions=(list(request.mm_positions)
                          if request.mm_positions else None),
            mm_embeddings=request.mm_embeddings,
        )
        if request.mm_positions:
            # content-addressed KV hashing: block hashes chain over ids
            # that fold in the image content, so the prefix cache can't
            # serve one image's KV for a prompt carrying another
            from ..tokens import TokenBlockSequence

            hash_ids = request.mm_hash_token_ids
            if hash_ids is None or len(hash_ids) != len(request.token_ids):
                raise ValueError(
                    "multimodal requests need mm_hash_token_ids aligned "
                    "with token_ids"
                )
            if (request.mm_embeddings is None
                    or len(request.mm_embeddings)
                    != len(request.mm_positions)):
                raise ValueError(
                    "mm_embeddings rows must match mm_positions"
                )
            seq.token_seq = TokenBlockSequence.from_tokens(
                list(hash_ids), self.config.block_size
            )
        if self.kvbm is not None or self.prefix is not None:
            # promote host-tier prefix blocks into G1 before admission so
            # the scheduler's prefix match serves them as native hits;
            # the token sequence is built once here and reused by the
            # scheduler (hash-chaining the prompt is O(prompt_len))
            from ..tokens import TokenBlockSequence

            if seq.token_seq is None:  # mm requests pre-built theirs
                seq.token_seq = TokenBlockSequence.from_tokens(
                    seq.prompt_ids, self.config.block_size
                )
            try:
                if self.prefix is not None:
                    # peer-G1 device-plane pull, then the KVBM tier chain
                    await self.prefix.onboard(seq.token_seq)
                else:
                    await self.kvbm.onboard_prefix(seq.token_seq)
            except Exception:
                log.exception("kvbm onboard failed — prefilling from scratch")
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[seq.seq_id] = queue
        self._seqs[seq.seq_id] = seq
        self.scheduler.add(seq)
        self._wake.set()
        try:
            while True:
                out = await queue.get()
                yield out
                if out.finished:
                    return
        finally:
            self._drop(seq)

    def _ap_mark_dead(self, slot: int) -> None:
        """Autopilot hook (overridden by the JAX engine): a seat whose seq
        finished must be killed on device before its blocks recycle."""

    def abort(self, seq_id: str, reason: str = "cancelled") -> None:
        seq = self._seqs.get(seq_id)
        if seq is not None and seq.status != SeqStatus.FINISHED:
            self._ap_mark_dead(seq.slot)
            self.scheduler.abort(seq, reason)
            self._emit_finish(seq, reason)

    # --------------- disaggregated prefill/decode hooks ----------------
    # (ref: the decode/prefill handler split in components/backends/vllm/
    #  src/dynamo/vllm/handlers.py:89,207 — here the engine itself exposes
    #  the hold/reserve/resume seams the reference gets from vLLM's
    #  kv_transfer connector)

    async def prefill_held(self, request: Request):
        """Prefill-worker side: run the prompt to its first token, keeping
        the KV blocks alive for extraction. Returns (seq, first_token);
        caller must ``release_held(seq)`` after extracting."""
        await self.start()
        if not request.token_ids:
            raise ValueError("empty prompt")
        seq = SchedSeq(
            seq_id=request.request_id or f"seq-{next(self._ids)}",
            prompt_ids=list(request.token_ids),
            max_tokens=1,
            eos_token_ids=frozenset(),
            temperature=request.temperature,
            top_k=request.top_k,
            top_p=request.top_p,
            seed=_seed31(request.seed),
            hold_blocks=True,
        )
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[seq.seq_id] = queue
        self._seqs[seq.seq_id] = seq
        self.scheduler.add(seq)
        self._wake.set()
        try:
            out = await queue.get()
        except asyncio.CancelledError:
            # Hard-cancelled mid-prefill (queue worker killed, caller
            # torn down): the held handle never reaches the caller, so
            # nobody can release_held — drop the hold ourselves. With
            # hold_blocks cleared, _finish/reap free the blocks the
            # moment no in-flight window can still scatter into them.
            seq.hold_blocks = False
            if seq.status == SeqStatus.FINISHED:
                if seq.pending_total == 0 and seq not in self.scheduler.zombies:
                    self.scheduler.release_held(seq)
            else:
                self.abort(seq.seq_id, "cancelled")
            self._queues.pop(seq.seq_id, None)
            self._seqs.pop(seq.seq_id, None)
            raise
        if out.finish_reason not in ("length", "stop"):
            self.release_held(seq)
            raise RuntimeError(
                f"remote prefill failed: {out.finish_reason}"
            )
        return seq, out.token_id

    def release_held(self, seq: SchedSeq) -> None:
        self.scheduler.release_held(seq)
        self._queues.pop(seq.seq_id, None)
        self._seqs.pop(seq.seq_id, None)

    def reserve_sequence(self, request: Request) -> Optional[SchedSeq]:
        """Decode-worker side: pre-allocate prompt blocks for KV injection.
        Returns None when the pool can't host the prompt right now (caller
        falls back to local prefill)."""
        seq = SchedSeq(
            seq_id=request.request_id or f"seq-{next(self._ids)}",
            prompt_ids=list(request.token_ids),
            max_tokens=max(1, request.max_tokens),
            eos_token_ids=(frozenset() if request.ignore_eos
                           else frozenset(request.eos_token_ids)),
            temperature=request.temperature,
            top_k=request.top_k,
            top_p=request.top_p,
            seed=_seed31(request.seed),
        )
        if not self.scheduler.reserve(seq):
            return None
        # epoch-guard the reservation: any transfer targeting these blocks
        # must present this epoch, so a delayed write aimed at a recycled
        # reservation (same seq id, new blocks) is rejected, not scattered
        seq.kv_epoch = next(self._kv_epoch)
        self._kv_reservations[seq.seq_id] = seq.kv_epoch
        self._queues[seq.seq_id] = asyncio.Queue()
        self._seqs[seq.seq_id] = seq
        return seq

    def cancel_reservation(self, seq: SchedSeq) -> None:
        self._kv_reservations.pop(seq.seq_id, None)
        self.scheduler.release_held(seq)  # reserved blocks, same release
        self._queues.pop(seq.seq_id, None)
        self._seqs.pop(seq.seq_id, None)

    def reservation_valid(self, seq_id: str, epoch: int) -> bool:
        """True while ``seq_id``'s reservation is live *and* carries
        ``epoch``. Both the device-plane scatter and the wire-relay inject
        check this immediately before writing; it also tells the orphan
        sweeper a reservation is still safe to cancel."""
        return self._kv_reservations.get(seq_id) == epoch

    async def resume_prefilled(
        self, seq: SchedSeq, first_token: int
    ) -> AsyncIterator[StepOutput]:
        """Decode-worker side: activate a reserved sequence whose KV was
        injected; streams from the remotely-sampled first token onward."""
        await self.start()
        # the reservation window closes here: late transfers must not write
        # into a sequence that is actively decoding
        self._kv_reservations.pop(seq.seq_id, None)
        self.scheduler.admit_prefilled(seq, first_token)
        self._emit_token(seq)
        self._wake.set()
        queue = self._queues[seq.seq_id]
        try:
            while True:
                out = await queue.get()
                yield out
                if out.finished:
                    return
        finally:
            self._drop(seq)

    def _drop(self, seq: SchedSeq) -> None:
        if seq.status != SeqStatus.FINISHED:
            self.scheduler.abort(seq, "cancelled")
        self._queues.pop(seq.seq_id, None)
        self._seqs.pop(seq.seq_id, None)

    # --------------------- AsyncEngine (wire) --------------------------

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        """Wire-format adapter: dict in, dict stream out."""
        mm = request.get("mm") or {}
        mm_embeddings = None
        if mm:
            from ..multimodal.encoder import array_from_wire

            mm_embeddings = array_from_wire(mm["embeddings"])
        req = Request(
            request_id=context.id,
            token_ids=list(request["token_ids"]),
            max_tokens=int(request.get("max_tokens", 64)),
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            top_p=float(request.get("top_p", 1.0) or 1.0),
            seed=request.get("seed"),
            eos_token_ids=tuple(request.get("eos_token_ids", ())),
            ignore_eos=bool(request.get("ignore_eos", False)),
            mm_positions=(list(mm["positions"]) if mm else None),
            mm_embeddings=mm_embeddings,
            mm_hash_token_ids=(list(mm["hash_token_ids"]) if mm else None),
        )
        async def _on_stop() -> None:
            await context.wait_stopped()
            self.abort(req.request_id,
                       "killed" if context.is_killed() else "cancelled")

        watcher = asyncio.create_task(_on_stop())
        t_submit = time.monotonic()
        seq_ref: Optional[SchedSeq] = None
        # landed -> this task holds the token (unpack, postprocess, the
        # queue, the loop's backlog): [sum, max], floats only, and only
        # where an exporter may take the span that carries them
        timed = tracing.get_tracer().keeps(context.trace.trace_id)
        wake = [0.0, 0.0]
        try:
            async for out in self.submit(req):
                if timed and out.t_land is not None:
                    lag = time.monotonic() - out.t_land
                    wake[0] += lag
                    if lag > wake[1]:
                        wake[1] = lag
                if seq_ref is None:
                    # grab the scheduler-side state before _drop can pop it;
                    # its t_scheduled/t_first_token stamps feed the spans
                    seq_ref = self._seqs.get(req.request_id)
                if context.is_killed():
                    return
                yield {
                    "token_ids": [out.token_id],
                    "index": out.index,
                    "finished": out.finished,
                    "finish_reason": out.finish_reason,
                    "num_prompt_tokens": out.num_prompt_tokens,
                }
                if out.finished:
                    return
        finally:
            watcher.cancel()
            self._record_stage_spans(context, t_submit, seq_ref, wake)

    def _record_stage_spans(
        self, context: Context, t_submit: float, seq: Optional[SchedSeq],
        wake: Tuple[float, float] = (0.0, 0.0),
    ) -> None:
        """Attribute engine time to worker.queue / engine.prefill /
        engine.decode spans from the scheduler's monotonic stamps. Recorded
        after the fact (no live span objects in the step loop) so the
        per-token hot path carries zero tracing overhead."""
        tracer = tracing.get_tracer()
        end = time.monotonic()
        t_sched = seq.t_scheduled if seq is not None else None
        t_first = seq.t_first_token if seq is not None else None
        q_attrs = None
        if seq is not None:
            q_attrs = {"prompt_tokens": seq.prompt_len,
                       "cached_tokens": seq.cached_tokens}
        tracer.record("worker.queue", context, start_mono=t_submit,
                      end_mono=(t_sched or end), attrs=q_attrs)
        if t_sched is not None:
            # the prompt-completing chunk's enqueue and its landing on the
            # fetch thread split the span into: chunks before it, device
            # queue + prefill program + D2H, and the hop to the first emit
            events = [(t, name) for name, t in (
                ("dispatched", seq.t_dispatched), ("landed", seq.t_landed),
            ) if t is not None]
            tracer.record("engine.prefill", context, start_mono=t_sched,
                          end_mono=(t_first or end), events=events)
        if t_first is not None:
            attrs = {"num_tokens": len(seq.output_ids)}
            if wake[0] > 0:   # absent, not zero, where no fetch was stamped
                attrs["wake_sum_s"], attrs["wake_max_s"] = wake
            if getattr(self, "spec_stats", None) is not None:
                attrs["spec_drafted"] = seq.spec_drafted
                attrs["spec_accepted"] = seq.spec_accepted
            tracer.record("engine.decode", context, start_mono=t_first,
                          end_mono=end, attrs=attrs)

    # --------------------- flight recorder surface ---------------------

    def obs_snapshot(self) -> dict:
        """One merged dict of live recorder gauges (stepstats window) and
        compile-watchdog counters; {} when the recorder is disabled."""
        if self.obs is None:
            return {}
        from ..observability import compilewatch
        snap = self.obs.snapshot()
        snap.update(compilewatch.snapshot())
        snap["stalls_total"] = self.num_stalls
        snap["stall_dead"] = int(self.stall_dead)
        snap["stall_quarantined_shapes"] = len(self._shape_quarantine)
        snap["pressure_level"] = self.pressure_level
        snap["pressure_peak"] = self.pressure_peak
        snap["pressure_spills_total"] = self.num_pressure_spills
        snap["pressure_shed_total"] = self.num_pressure_shed
        return snap

    def mark_obs_warmup_done(self) -> None:
        """Drop warmup steps from the window and arm the steady-state
        recompile watchdog. Call after warmup traffic has drained."""
        if self.obs is None:
            return
        from ..observability import compilewatch
        self.obs.mark_warmup_done()
        compilewatch.mark_warmup_done()

    # ------------------------- step loop -------------------------------

    async def _execute_batch_async(self, batch) -> Tuple[List[int], List[int]]:
        """Execute one scheduled batch; returns (prefill, decode) samples."""
        raise NotImplementedError

    async def _run_loop(self) -> None:
        self.loop_clock = _LoopClock(loop_busy.install())
        if self.pipeline_depth > 1:
            await self._run_loop_pipelined()
        else:
            await self._run_loop_sync()

    async def _run_loop_pipelined(self) -> None:
        """Run-ahead loop: schedule and dispatch window N+1 while window N
        is still computing/fetching. Decode input tokens ride the device
        token ring, so no dispatch ever waits on a host fetch; sampled
        tokens are observed one-plus windows behind for emission and stop
        checks. Landings are applied strictly in dispatch order."""
        inflight: Deque[Tuple[Any, Any]] = deque()
        clock = self.loop_clock

        async def land_next() -> None:
            batch0, fut = inflight.popleft()
            try:
                with _phase("engine.wait_land"), clock.waiting("land"):
                    results = await asyncio.wait_for(
                        self._landing(batch0, fut),
                        self._stall_deadline(batch0),
                    )
            except asyncio.TimeoutError:
                # the head landing blew its deadline: every younger window
                # reads the wedged window's ring state, so the whole
                # run-ahead pipeline is cancelled and recovered together
                wedged = [batch0]
                self._swallow_future(fut)
                while inflight:
                    b, f = inflight.popleft()
                    wedged.append(b)
                    self._swallow_future(f)
                self._on_stall(wedged)
                return
            except Exception:
                log.exception("window failed; aborting its seqs")
                self._abort_batch(batch0)
                return
            self._stall_streak = 0
            with _phase("engine.postprocess"):
                try:
                    self._postprocess(batch0, results)
                except Exception:
                    log.exception("postprocess failed")
                self._flush_kv_events()

        while not self._stopped:
            while inflight and inflight[0][1].done():
                await land_next()
            with _phase("engine.schedule"):
                self._pressure_tick()
                batch = self.scheduler.schedule()
                self._mark_preempted_seats(batch)
            if batch.is_empty:
                if inflight:
                    await land_next()
                    continue
                if self.scheduler.waiting and not self.scheduler.running:
                    seq = self.scheduler.waiting[0]
                    log.error("seq %s cannot fit in KV pool — failing",
                              seq.seq_id)
                    self.scheduler.abort(seq, "error")
                    self._emit_finish(seq, "error")
                    continue
                self._wake.clear()
                if self.kvbm is not None:
                    try:
                        while (not self._wake.is_set()
                               and await self.kvbm.tick()):
                            pass
                    except Exception:
                        log.exception("kvbm idle drain failed")
                if self._stopped:
                    break
                with _phase("engine.wait_idle"), clock.waiting("idle"):
                    await self._wake.wait()
                continue
            self._arm_stall_fault(batch)
            (batch.host_s, batch.loop_busy_s,
             batch.loop_cpu_s) = clock.handoff()
            try:
                with clock.waiting("executor"):
                    fut = await self._dispatch_batch_async(batch)
            except Exception:
                log.exception("dispatch failed; aborting scheduled seqs")
                self._abort_batch(batch)
                continue
            inflight.append((batch, fut))
            while len(inflight) >= self.pipeline_depth:
                await land_next()
            if self.kvbm is not None:
                try:
                    await self.kvbm.tick()
                except Exception:
                    log.exception("kvbm offload tick failed")
        while inflight:  # drain so stop() leaves consistent bookkeeping
            await land_next()

    def _abort_batch(self, batch) -> None:
        """Fail every seq a dispatched-or-dispatching batch touches and
        clear the speculative pendings it registered. Seats are marked
        dead BEFORE the abort releases blocks — otherwise the device
        autopilot keeps scattering into recycled blocks."""
        for chunk in batch.prefills:
            seq = chunk.seq
            self.scheduler.on_tokens_discarded(
                seq, 0, first=chunk.final, prompt=chunk.length
            )
            if seq.status != SeqStatus.FINISHED:
                self._ap_mark_dead(seq.slot)
                self.scheduler.abort(seq, "error")
                self._emit_finish(seq, "error")
        for row in batch.decode_rows:
            seq = row.seq
            self.scheduler.on_tokens_discarded(seq, row.accepted)
            if seq.status != SeqStatus.FINISHED:
                self._ap_mark_dead(row.slot)
                self.scheduler.abort(seq, "error")
                self._emit_finish(seq, "error")

    async def _dispatch_batch_async(self, batch):
        """Enqueue the batch's device work; resolve to a future of fetched
        results. Overridden by the JAX engine; the base class executes
        synchronously (mocker paths keep pipeline_depth 1)."""
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        try:
            fut.set_result(await self._execute_batch_async(batch))
        except Exception as e:  # pragma: no cover
            fut.set_exception(e)
        return fut

    def _mark_preempted_seats(self, batch) -> None:
        """A preempted seq's blocks were just released — its device seat
        must die before they recycle, even if this batch is otherwise
        empty (the kill rides the next dispatch, which in-order precedes
        any reuse)."""
        for seq in batch.preempted:
            if seq.preempted_slot >= 0:
                self._ap_mark_dead(seq.preempted_slot)
                seq.preempted_slot = -1

    # ----------------------- stall watchdog ----------------------------
    # A wedged device dispatch (deadlocked collective, runaway recompile,
    # driver hang) would otherwise freeze the loop forever: every queued
    # request hangs and the worker looks alive to the router. The watchdog
    # bounds each landing by a deadline scaled to the window's token count,
    # cancels the wedged window, quarantines the shape class that wedged,
    # and replays the touched seats from their journal (prompt + emitted
    # tokens) — bounded retries per seat, bounded streak per worker.

    def _stall_deadline(self, batch) -> Optional[float]:
        """Deadline for one landing; None disables (stall_timeout_s <= 0).
        Scales with scheduled work so big prefill windows aren't false
        positives at the same setting that catches a wedged decode."""
        base = self.config.stall_timeout_s
        if base <= 0:
            return None
        n = sum(c.length for c in batch.prefills)
        n += sum(r.accepted for r in batch.decode_rows)
        return base + self.config.stall_timeout_per_token_s * n

    def _arm_stall_fault(self, batch) -> None:
        """Fault-registry seam: a ``delay`` rule on ``engine.stall`` wedges
        this window's landing for delay_s, as a hung device dispatch would.
        The key leads with the window kind (``decode``/``prefill``/``mixed``)
        so a rule can pin the wedge to a window class: the watchdog deadline
        scales with scheduled tokens, so only a wedge longer than that
        window's deadline is ever *detected* — matching ``decode`` keeps a
        finite delay reliably above the (small) pure-decode deadline."""
        if batch.prefills:
            kind = "mixed" if batch.decode_rows else "prefill"
        else:
            kind = "decode"
        rule = faults.active(
            "engine.stall", f"{kind}:{next(self._window_seq)}")
        if rule is not None and rule.kind == faults.DELAY:
            batch.stall_inject_s = rule.delay_s

    async def _landing(self, batch, fut):
        inject = getattr(batch, "stall_inject_s", 0.0)
        if inject:
            await asyncio.sleep(inject)  # seeded engine.stall wedge
        return await fut

    @staticmethod
    def _swallow_future(fut) -> None:
        """Detach from a wedged future: request cancellation and retrieve
        any late exception so abandoned windows never log
        'exception was never retrieved'."""
        fut.cancel()
        fut.add_done_callback(
            lambda f: f.exception() if not f.cancelled() else None
        )

    def _shape_bucket(self, kind: str, n: int) -> int:
        """Bucket used for stall attribution; the JAX engine maps through
        its dispatch bucket ladders."""
        return n

    def _quarantine_shape(self, cls) -> None:
        if cls not in self._shape_quarantine:
            self._shape_quarantine.add(cls)
            log.warning(
                "stall watchdog: quarantined shape class %s:%s", *cls
            )

    def _batch_shape_classes(self, batch) -> set:
        classes = set()
        for chunk in batch.prefills:
            classes.add(
                ("prefill", self._shape_bucket("prefill", chunk.length))
            )
        if batch.decode_rows:
            classes.add(
                ("decode", self._shape_bucket("decode",
                                              len(batch.decode_rows)))
            )
        return classes

    def _on_stall(self, batches) -> None:
        """A landing blew its deadline. Attribute the wedge to the head
        window's shape classes (cross-checked against the compile watchdog's
        last label in the log line), quarantine them, recover every touched
        seat, and track the streak toward declaring the worker dead."""
        self.num_stalls += 1
        self._stall_streak += 1
        classes = self._batch_shape_classes(batches[0])
        label = ""
        if self.obs is not None:
            try:
                from ..observability import compilewatch
                snap = compilewatch.snapshot()
                label = snap.get("last_compile_key", "") or ""
            except Exception:
                label = ""
        log.error(
            "dispatch stall: landing blew its deadline (shape classes %s, "
            "last compile %r, streak %d/%d)",
            sorted(classes), label, self._stall_streak,
            self.config.stall_dead_threshold,
        )
        for cls in classes:
            self._quarantine_shape(cls)
        self._recover_batches(batches)
        if self._stall_streak >= self.config.stall_dead_threshold:
            self.stall_dead = True
            log.error(
                "stall streak hit %d — declaring worker dead",
                self._stall_streak,
            )
            for seq in list(self._seqs.values()):
                if seq.status != SeqStatus.FINISHED:
                    self._ap_mark_dead(seq.slot)
                    self.scheduler.abort(seq, "error")
                    self._emit_finish(seq, "error")

    def _recover_batches(self, batches) -> None:
        """Cancel wedged windows: discard every pending they registered
        (mirroring _abort_batch), then requeue each touched live seat for
        journal replay — a recompute preemption whose 'journal' is the
        seq's own prompt + emitted tokens, giving byte-identical resumption
        under the seq's seed. Seats over stall_seq_retries fail instead."""
        touched: Dict[str, SchedSeq] = {}
        for batch in batches:
            for chunk in batch.prefills:
                self.scheduler.on_tokens_discarded(
                    chunk.seq, 0, first=chunk.final, prompt=chunk.length
                )
                touched[chunk.seq.seq_id] = chunk.seq
            for row in batch.decode_rows:
                self.scheduler.on_tokens_discarded(row.seq, row.accepted)
                touched[row.seq.seq_id] = row.seq
        for seq in touched.values():
            if seq.status == SeqStatus.FINISHED or seq.pending_total != 0:
                continue
            retries = self._stall_retries.get(seq.seq_id, 0) + 1
            self._stall_retries[seq.seq_id] = retries
            if retries > self.config.stall_seq_retries:
                self._ap_mark_dead(seq.slot)
                self.scheduler.abort(seq, "error")
                self._emit_finish(seq, "error")
                continue
            if seq.status is SeqStatus.WAITING:
                continue  # never held blocks — already queued for replay
            slot = self.scheduler.preempt_recompute(seq)
            self._ap_mark_dead(slot)

    # ---------------------- HBM-pressure ladder ------------------------

    def _pressure_tick(self) -> None:
        """Graduated response to KV-pool pressure, one check per loop pass:
        rung 1 spills the coldest seat (recompute preemption — its sealed
        blocks stay evictable in the prefix cache / kvbm host tier), rung 2
        pauses speculative decoding (frees draft lookahead), rung 3 sheds
        new admissions. Rungs release with pressure_release hysteresis so
        the ladder doesn't flap at a threshold."""
        cfg = self.config
        spill_t = cfg.pressure_spill_threshold
        spec_t = cfg.pressure_spec_threshold
        shed_t = cfg.pressure_shed_threshold
        if spill_t <= 0 and spec_t <= 0 and shed_t <= 0:
            return
        usage = self.scheduler.pool.usage
        release = cfg.pressure_release
        if shed_t > 0:
            if not self.pressure_shedding and usage >= shed_t:
                self.pressure_shedding = True
                log.warning(
                    "pressure ladder: shedding admissions "
                    "(pool usage %.2f >= %.2f)", usage, shed_t,
                )
            elif self.pressure_shedding and usage < shed_t - release:
                self.pressure_shedding = False
                log.info(
                    "pressure ladder: admissions reopened (pool usage %.2f)",
                    usage,
                )
        if spec_t > 0:
            if not self._pressure_spec_paused and usage >= spec_t:
                self._pressure_spec_paused = True
                self._pause_spec()
            elif self._pressure_spec_paused and usage < spec_t - release:
                self._pressure_spec_paused = False
                self._resume_spec()
        if self._pressure_spill_cool > 0:
            self._pressure_spill_cool -= 1
        if (spill_t > 0 and usage >= spill_t
                and self._pressure_spill_cool == 0):
            victim = self.scheduler._pick_victim(None)
            if victim is not None and victim.pending_total == 0:
                slot = self.scheduler.preempt_recompute(victim)
                self._ap_mark_dead(slot)
                self.num_pressure_spills += 1
                # cooldown bounds churn: the spilled seat re-prefills
                # (mostly prefix hits) before another spill is considered
                self._pressure_spill_cool = 4
                log.info(
                    "pressure ladder: spilled seq %s (pool usage %.2f)",
                    victim.seq_id, usage,
                )
        self.pressure_level = (
            3 if self.pressure_shedding
            else 2 if self._pressure_spec_paused
            else 1 if (spill_t > 0 and usage >= spill_t)
            else 0
        )
        if self.pressure_level > self.pressure_peak:
            self.pressure_peak = self.pressure_level

    def _pause_spec(self) -> None:
        """Rung 2 hook; the JAX engine narrows the spec plan window."""

    def _resume_spec(self) -> None:
        pass

    # --------------------- preemption / evacuation ---------------------
    # (runtime.preemption drives these: park a decoding seat, wait for its
    #  inflight windows to land, stream its KV to a peer, finish it here)

    def evacuable_seats(self) -> List[SchedSeq]:
        """Decoding seats whose KV is worth moving (prefill complete).
        PREFILL/WAITING seats are cheaper to re-prefill at the destination
        than to stream mid-build."""
        return [s for s in self.scheduler.running
                if s.status is SeqStatus.RUNNING and s.prefill_done]

    def park_for_evacuation(self, seq_id: str) -> Optional[SchedSeq]:
        """Freeze a seat for KV evacuation: the scheduler plans no new
        windows for it and never picks it as a recompute victim, so its
        blocks stay byte-stable while the transfer reads them."""
        seq = self._seqs.get(seq_id)
        if seq is None or seq.status is not SeqStatus.RUNNING:
            return None
        seq.status = SeqStatus.EVACUATING
        return seq

    def unpark(self, seq: SchedSeq) -> None:
        """Abort an evacuation: the seat resumes decoding locally."""
        if seq.status is SeqStatus.EVACUATING:
            seq.status = SeqStatus.RUNNING
            self._wake.set()

    async def wait_quiesced(
        self, seq: SchedSeq, timeout_s: float = 10.0
    ) -> bool:
        """Wait until none of the seat's tokens are in an inflight window —
        only then is its KV byte-stable and safe to read."""
        deadline = time.monotonic() + timeout_s
        while seq.pending_total > 0:
            if time.monotonic() >= deadline:
                return False
            await asyncio.sleep(0.005)
        return True

    def finish_evacuated(self, seq: SchedSeq) -> None:
        """The seat now lives on the receiving worker: kill the device seat
        and close the local stream with finish_reason ``evacuated``."""
        if seq.status is SeqStatus.FINISHED:
            return
        self._ap_mark_dead(seq.slot)
        self.scheduler.abort(seq, "evacuated")
        self._emit_finish(seq, "evacuated")

    async def _run_loop_sync(self) -> None:
        clock = self.loop_clock
        while not self._stopped:
            with _phase("engine.schedule"):
                self._pressure_tick()
                batch = self.scheduler.schedule()
                self._mark_preempted_seats(batch)
            if batch.is_empty:
                # a waiting request that can never fit (pool smaller than its
                # prompt) would hang forever — fail it rather than deadlock
                if self.scheduler.waiting and not self.scheduler.running:
                    seq = self.scheduler.waiting[0]
                    log.error("seq %s cannot fit in KV pool — failing",
                              seq.seq_id)
                    self.scheduler.abort(seq, "error")
                    self._emit_finish(seq, "error")
                    continue
                # clear BEFORE the kvbm drain: a submit() arriving during the
                # drain's awaits sets _wake, which must survive to the wait()
                self._wake.clear()
                if self.kvbm is not None:
                    try:  # going idle: drain the offload backlog
                        while (not self._wake.is_set()
                               and await self.kvbm.tick()):
                            pass
                    except Exception:
                        log.exception("kvbm idle drain failed")
                if self._stopped:
                    return
                with _phase("engine.wait_idle"), clock.waiting("idle"):
                    await self._wake.wait()
                continue
            self._arm_stall_fault(batch)
            (batch.host_s, batch.loop_busy_s,
             batch.loop_cpu_s) = clock.handoff()
            inner = asyncio.ensure_future(self._execute_batch_async(batch))
            try:
                # one executor turn dispatches AND fetches here, so the
                # whole wait is the landing's
                with _phase("engine.wait_land"), clock.waiting("land"):
                    results = await asyncio.wait_for(
                        self._landing(batch, inner),
                        self._stall_deadline(batch),
                    )
            except asyncio.TimeoutError:
                self._swallow_future(inner)
                self._on_stall([batch])
                continue
            except Exception:
                log.exception("engine step failed; aborting scheduled seqs")
                # _abort_batch also clears the speculative pendings that
                # schedule() registered — plain abort would park the seqs
                # as never-reaped zombies, leaking blocks and ring slots
                self._abort_batch(batch)
                continue
            self._stall_streak = 0
            with _phase("engine.postprocess"):
                try:
                    self._postprocess(batch, results)
                except Exception:
                    # bookkeeping must never kill the step loop — every
                    # queued request would hang forever
                    log.exception("postprocess failed")
                self._flush_kv_events()
            if self.kvbm is not None:
                try:
                    await self.kvbm.tick()
                except Exception:
                    log.exception("kvbm offload tick failed")

    def _postprocess(self, batch, results) -> None:
        """Apply step results. Decode samples are per-seq token WINDOWS
        (length >= 1); tokens after a mid-window finish are discarded (and
        their speculative pendings cleared so zombie seqs get reaped)."""
        prefill_samples, decode_samples = results
        self.num_steps += 1
        for chunk, sampled in zip(batch.prefills, prefill_samples):
            seq = chunk.seq
            if seq.status == SeqStatus.FINISHED:
                # aborted while the chunk was in flight
                self.scheduler.on_tokens_discarded(
                    seq, 0, first=chunk.final, prompt=chunk.length
                )
                continue
            self.scheduler.on_prefill_executed(
                chunk, sampled if chunk.final else None
            )
            if chunk.final:
                self._emit_token(seq, batch.t_landed)
        for i, row in enumerate(batch.decode_rows):
            seq = row.seq
            window = decode_samples[i]
            if isinstance(window, int):
                window = [window]
            applied = 0
            for tok in window[:row.accepted]:
                if seq.status == SeqStatus.FINISHED:
                    break  # aborted / stopped mid-window
                self.scheduler.on_decode_executed(seq, tok)
                applied += 1
                self._emit_token(seq, batch.t_landed)
            if applied < row.accepted:
                self.scheduler.on_tokens_discarded(
                    seq, row.accepted - applied
                )
            if seq.status == SeqStatus.FINISHED:
                self._ap_mark_dead(row.slot)

    def _emit_token(self, seq: SchedSeq,
                    t_land: Optional[float] = None) -> None:
        self.num_generated_tokens += 1
        if seq.t_first_token is None:
            seq.t_first_token = time.monotonic()
        reason = self.scheduler.check_stop(seq)
        out = StepOutput(
            request_id=seq.seq_id,
            token_id=seq.output_ids[-1],
            index=len(seq.output_ids) - 1,
            finished=reason is not None,
            finish_reason=reason,
            num_prompt_tokens=seq.prompt_len,
            t_land=t_land,
        )
        if reason is not None:
            self.scheduler.finish(seq, reason)
        q = self._queues.get(seq.seq_id)
        if q is not None:
            q.put_nowait(out)

    def _emit_finish(self, seq: SchedSeq, reason: str) -> None:
        q = self._queues.get(seq.seq_id)
        if q is not None:
            q.put_nowait(StepOutput(
                request_id=seq.seq_id,
                token_id=seq.output_ids[-1] if seq.output_ids else -1,
                index=max(0, len(seq.output_ids) - 1),
                finished=True,
                finish_reason=reason,
                num_prompt_tokens=seq.prompt_len,
            ))

    # ------------------------- kv events -------------------------------

    def _on_kv_event(self, event: KvEvent) -> None:
        self._pending_events.append(event.to_dict())
        if len(self._pending_events) > 10000:
            del self._pending_events[:5000]
        if self.kvbm is not None:
            self.kvbm.on_pool_event(event)
        if self.prefix is not None:
            self.prefix.on_pool_event(event)

    def _flush_kv_events(self) -> None:
        if self.kv_event_sink is None:
            return
        events, self._pending_events = self._pending_events, []
        for e in events:
            try:
                self.kv_event_sink(e)
            except Exception:
                log.exception("kv event sink failed")


class InferenceEngine(EngineCore):
    """The JAX device engine: jitted unified prefill/decode steps over a
    paged HBM KV cache, dispatched from a dedicated executor thread so the
    event loop never blocks on XLA."""

    def __init__(
        self,
        model_config: ModelConfig,
        engine_config: EngineConfig,
        params: Optional[model_lib.Params] = None,
        seed: int = 0,
        devices: Optional[list] = None,
    ):
        # what the engine may not assume of a sequence's memory: layers that
        # keep something other than K and V pages, and a state a seat
        self._unpaged = model_config.cache_kinds != ("kv",)
        self._seat_state = model_config.has_seat_state
        self._latent = model_config.has_latent_cache
        if self._unpaged:
            if engine_config.kv_dtype != "bf16":
                raise ValueError(
                    f"--kv-dtype {engine_config.kv_dtype} quantises K and V "
                    f"pages; cache kinds "
                    f"{list(model_config.cache_kinds)} have no quantised "
                    f"form")
            if engine_config.sp_prefill_threshold > 0:
                model_lib.refuse_unpaged(
                    model_config, "the sequence-parallel ring prefill")
        if engine_config.prefill_chunk_tokens > 0:
            pct = max(engine_config.prefill_chunk_tokens,
                      engine_config.block_size)
            cap = min(pct, max(engine_config.prefill_buckets))
            bucket = min(
                (b for b in engine_config.prefill_buckets if b >= cap),
                default=max(engine_config.prefill_buckets),
            )
            log.info(
                "chunked prefill: prompts admitted in %d-token chunks "
                "interleaved with decode", cap,
            )
            if bucket != cap:
                # every chunk pads up to a compiled bucket; a cap off the
                # bucket grid silently burns the difference each dispatch
                log.warning(
                    "prefill_chunk_tokens=%d is not a prefill bucket — "
                    "chunks pad to the %d bucket (%d wasted tokens each); "
                    "consider a bucket-sized cap %r",
                    cap, bucket, bucket - cap,
                    engine_config.prefill_buckets,
                )
        super().__init__(engine_config)
        self.model_config = model_config
        self.scheduler.seat_state = self._seat_state
        self.pp = engine_config.pp_stages
        if params is None and self.pp > 1:
            params = model_lib.init_params(
                jax.random.PRNGKey(seed), model_config
            )
        self._sp_prefill_fn = None
        self._mm_prefill_fn = None  # built lazily on the first mm request
        # a table's window kind (0: none), for the window counters, and
        # whether its decode windows carry the routing counters' row
        self._attn_window = max(k.window for k in model_config.attn_kinds)
        self._moe_stats = model_config.has_routed_experts
        self.num_sp_prefills = 0
        self.num_mm_prefills = 0
        if self.pp > 1:
            # pipeline-parallel serving: layers stage-sharded over a pp
            # mesh, stacked cache, GPipe-microbatched unified step
            from ..parallel import pp_serving

            self.mesh = pp_serving.make_pp_mesh(self.pp, devices)
            self.params = jax.device_put(
                params, pp_serving.pp_param_shardings(self.mesh,
                                                      model_config)
            )
            self.cache = jax.device_put(
                pp_serving.init_pp_cache(model_config, engine_config),
                pp_serving.pp_cache_shardings(self.mesh, model_config),
            )
            self._step_fn = compilewatch.label(
                pp_serving.make_pp_step_fn(
                    model_config, engine_config, self.mesh,
                    engine_config.pp_microbatches,
                ),
                "pp_step",
            )
        else:
            self.mesh = model_lib.make_mesh(
                engine_config.mesh_shape, devices
            )
            if params is None:
                # random weights from the seed, born in the serving layout
                # (never whole on one device)
                self.params = model_lib.init_params_sharded(
                    jax.random.PRNGKey(seed), model_config, self.mesh,
                    engine_config.weight_dtype,
                )
            else:
                # quantize-at-init for host params; params streamed by
                # load_hf_params_sharded arrive already quantized (dict
                # leaves) and pass through unchanged
                params = quant.quantize_params(
                    params, engine_config.weight_dtype
                )
                self.params = model_lib.shard_params(
                    params, self.mesh, model_config,
                    engine_config.weight_dtype,
                )
            self.cache = model_lib.init_cache_sharded(
                model_config, engine_config, self.mesh
            )
            # pipelined serving path: packed ring prefill + autopilot
            # decode windows running on device-resident control state
            self._ap_Wcap = engine_config.max_blocks_per_seq
            # what StepRecord.kv_blocks_walked counts with: the decode
            # kernel's tile as the window traces it (0 = the einsum path,
            # which gathers the table's whole width)
            self._decode_kv_tile = model_lib.decode_kv_tile(
                model_config, engine_config, self.mesh)
            self._ap_window_fn, self._ap_delta_fn = (
                model_lib.make_autopilot_fns(
                    model_config, engine_config, self._ap_Wcap, self.mesh,
                )
            )
            # speculative decoding: drafter history + draft/verify window
            self._spec_k = 0
            self._spec_hist_cap = 0
            self._spec_auto_disabled = False
            if engine_config.spec_mode == "ngram":
                self._spec_k = engine_config.spec_k
                self._spec_hist_cap = (engine_config.spec_hist_cap
                                       or engine_config.max_model_len)
                self._spec_window_fn, self._spec_hist_fill_fn = (
                    model_lib.make_spec_fns(
                        model_config, engine_config, self._spec_k,
                        engine_config.spec_ngram_min,
                        engine_config.spec_ngram_max, self.mesh,
                    )
                )
                from ..spec.stats import SpecDecodeStats
                self.spec_stats = SpecDecodeStats()
                # a spec window lands a DATA-DEPENDENT 1..k+1 tokens, so
                # run-ahead scheduling (which predicts the next window's
                # base) is off the table: force the synchronous loop
                if engine_config.pipeline_depth > 1:
                    log.info("spec_mode=ngram forces pipeline_depth=1")
                self.scheduler.spec_plan_window = self._spec_k + 1
            self._ctl = self._fresh_ctl(seed)
            # host mirror of per-slot device state + seat map
            self._packed_prefill_fns: Dict[Tuple[int, int], Any] = {}
            self.num_prefill_dispatches = 0
            self._ap: Dict[int, Dict[str, Any]] = {}
            self._ap_cols: List[int] = []       # device slot_rows content
            self._ap_rows_dev = None            # its device array
            self._ap_dead: set = set()          # slots to kill next dispatch
            self.pipeline_depth = max(1, engine_config.pipeline_depth)
            if engine_config.spec_mode == "ngram":
                self.pipeline_depth = 1
            if (engine_config.sp_prefill_threshold > 0
                    and self.mesh.devices.size > 1):
                self._sp_prefill_fn = model_lib.make_sp_ring_prefill_fn(
                    model_config, engine_config, self.mesh
                )
                self.scheduler.sp_enabled = True
        # per shape class the impl and tile the step programs are traced
        # with; nothing is timed to decide it
        self.attention_impl_choice = model_lib.attention_choice(
            model_config, engine_config, self.mesh)
        # flight recorder: per-step MFU/goodput accounting + the compile
        # watchdog. Records are stamped at dispatch/landing on arrays the
        # fetcher already syncs — no extra host round-trips.
        if env_flag("DYNTPU_OBS_ENABLED", True):
            dev0 = self.mesh.devices.flat[0]
            self.obs = StepStats(
                FlopsModel(model_config),
                n_chips=int(self.mesh.devices.size),
                peak_flops=obs_flops.peak_flops(
                    getattr(dev0, "device_kind", ""),
                    getattr(dev0, "platform", "cpu"),
                    # quantized weights run the matmuls at the int8/fp8
                    # roofline — MFU against the bf16 peak would flatter
                    engine_config.weight_dtype
                    if quant.is_quantized(engine_config.weight_dtype)
                    else model_config.dtype,
                ),
                window_s=env_float("DYNTPU_OBS_WINDOW_S", 10.0),
                jsonl_path=env_str("DYNTPU_OBS_STEPSTATS_PATH", ""),
            )
            compilewatch.install()
        self._rng = jax.random.PRNGKey(seed + 1)
        # one-shot einsum rebuild when the largest decode bucket stalls
        self._stall_einsum_fallback = False
        self._encode_fn = None  # built lazily on the first embed()
        self._mm_ring_fn = None  # lazy (pipelined mm prefill)
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpu-step"
        )
        # fetches (device_get of sampled-token handles) run OFF the
        # dispatch thread on the batching fetcher: a fetch is a host sync
        # (see _BatchingFetcher) and must never delay the next window's
        # enqueue; grouped gets keep the landing rate above the window
        # rate.
        self._fetcher = _BatchingFetcher(
            self._unpack_results, on_sync=self._count_fetch_sync
        )
        # multi-host: the leader's broadcaster observes every executed step
        # so followers can replay the identical jitted call sequence
        # (parallel/multihost.py); called on the executor thread
        self.step_sink: Optional[Callable[[str, Dict[str, np.ndarray]],
                                          None]] = None
        if self._seat_state and self.pp == 1:
            self._compile_step_programs(seed)
        if self.pp > 1 or self._unpaged:
            # the transfer ops assume the per-layer list cache of K and V
            # pages; disagg and KVBM on a pp engine, or for a table that
            # keeps a latent or a seat state, are future work
            self._kv_extract = self._kv_inject = None
        else:
            self._kv_extract, self._kv_inject = model_lib.make_kv_ops(
                model_config, engine_config, self.mesh
            )

    def _fresh_ctl(self, seed: int):
        """The decode windows' device-resident control state as a start
        finds it: every seat dead."""
        return jax.device_put(
            model_lib.init_ctl(
                self.config, self.config.max_num_seqs, self._ap_Wcap,
                seed=seed + 2, hist_cap=self._spec_hist_cap,
            ),
            layout.replicated(self.mesh),
        )

    def _prefill_table_width(self, nb: int) -> int:
        """The width ``W`` of the block table a prefill chunk that reaches
        ``nb`` blocks is fed with: the next power of two, so that the
        programs are O(log) of the context.  Where the model keeps a seat
        state the programs are compiled at start-up, all of them
        (:meth:`_compile_step_programs`), so the ladder is coarser: the
        table of the largest chunk, then powers of four (32, 128, 512 at 16
        tokens a block and chunks of 512): the width pads one gather and
        the latent layers' keys, the chunk's length pads every layer."""
        cap = self.config.max_blocks_per_seq
        if not self._seat_state:
            return _pow2_bucket(nb, cap)
        w = -(-max(self.config.prefill_buckets) // self.config.block_size)
        while w < min(nb, cap):
            w *= 4
        return min(w, cap)

    def _compile_step_programs(self, seed: int) -> None:
        """Compile every step program before the first request, for a model
        some of whose layers keep a seat state.

        Such a model gives no prefix hit, so nobody outside can start a
        chunk where they like: a ``(T, W)`` prefill program is reached only
        by a prompt of that very length, one compile (20-30 s on a v5e
        host at the benchmark's widths) in the middle of serving for each.
        So the engine reaches them itself: every prefill bucket at every
        table width, every decode bucket, every size of the control
        state's delta.  Each program runs once on a feed of pads (position
        -1, the trash seat, block 0, nothing written to ``last_tok``),
        which by :func:`model.forward`'s contract leaves the cache and the
        seats as they were; the control state is made anew behind it."""
        cfg = self.config
        trash = cfg.max_num_seqs
        t0 = time.monotonic()
        key = jax.random.PRNGKey(0)
        widths = sorted({self._prefill_table_width(nb)
                         for nb in range(1, cfg.max_blocks_per_seq + 1)})
        for T in cfg.prefill_buckets:
            for W in widths:
                fn = model_lib.make_packed_prefill_fn(
                    self.model_config, cfg, T, W, self.mesh)
                self._packed_prefill_fns[(T, W)] = fn
                pint = np.zeros((1, T + W + model_lib.PP_SCALARS), np.int32)
                pint[0, T + W + 2] = trash        # n = 0: every token a pad
                self.cache, last_tok, _ = fn(
                    self.params, self.cache, self._ctl["last_tok"], pint,
                    key)
                self._ctl = {**self._ctl, "last_tok": last_tok}
        for B in cfg.decode_buckets:
            self.cache, self._ctl, _ = self._ap_window_fn(
                self.params, self.cache, self._ctl,
                jax.device_put(np.full((B,), trash, np.int32)))
        n = 1
        while n <= _pow2_bucket(cfg.max_num_seqs):
            di = np.zeros((n, model_lib.CTL_I32_FIELDS + self._ap_Wcap),
                          np.int32)
            di[:, 0], di[:, 5] = trash, -1
            self._ctl = self._ap_delta_fn(self._ctl, di,
                                          np.zeros((n, 2), np.float32))
            n *= 2
        # at start-up, before any request: the compiles are done when the
        # last program has run
        jax.block_until_ready(self.cache)  # dynalint: disable=DT102
        self._ctl = self._fresh_ctl(seed)
        log.info(
            "seat state: %d prefill programs (T %r x W %r), %d decode "
            "buckets and the control deltas compiled in %.1f s",
            len(cfg.prefill_buckets) * len(widths), cfg.prefill_buckets,
            widths, len(cfg.decode_buckets), time.monotonic() - t0)

    def device_report(self) -> dict:
        """What this engine actually runs on and with — read by the
        worker's ready line and its system-server ``engine`` probe, so a
        launcher learns the device from the process that owns it instead
        of opening one itself.  Host-side reads only (no device sync)."""
        from .. import native
        from ..utils.device_env import compile_cache_stats

        devs = list(self.mesh.devices.flat)
        memory = []
        for d in devs:
            # another host's devices (multi-host mesh) have no stats here
            local = d.process_index == jax.process_index()
            ms = (d.memory_stats() or {}) if local else {}
            memory.append({
                "id": d.id,
                "bytes_in_use": ms.get("bytes_in_use"),
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                "bytes_limit": ms.get("bytes_limit"),
            })
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(jax.devices()),
            "mesh_device_ids": [d.id for d in devs],
            "mesh_shape": dict(self.mesh.shape),
            # per shape class, as last traced: impl / interpret / tile
            "attention": {k: dict(v) for k, v in
                          model_lib.ATTENTION_TRACES.items()},
            "attention_choice": self.attention_impl_choice,
            # a table's grouped matmuls, one entry a distinct call as traced
            # (rows < pairs: a slab of parallel.moe.held_rows)
            "expert_tiles": [
                {"pairs": p, "rows": r, "k": k, "n": n, "tile": list(tile)}
                for (p, r, k, n), tile in sorted(GMM_TILES_TRACED.items())],
            "native": native.implementation(),
            "compile_cache": compile_cache_stats(),
            "compile": compilewatch.snapshot(),
            "memory": memory,
            "last_profile": profiling.last_capture(),
        }

    def _shutdown_executor(self) -> None:
        self._executor.shutdown(wait=False)
        self._fetcher.stop()
        if self.obs is not None:
            self.obs.close()

    def _ap_mark_dead(self, slot: int) -> None:
        if self.pp == 1 and slot >= 0 and (
                slot in self._ap or slot in self._ap_cols):
            self._ap_dead.add(slot)

    # ------------------ KV block transfer (disagg) ---------------------
    # Both run on the single step executor thread, serialising them with
    # step execution — the cache buffer is donated every step, so nothing
    # may touch it concurrently.

    async def extract_kv_blocks(self, block_ids) -> Dict[str, np.ndarray]:
        """Gather arbitrary physical blocks to host memory ([L, N, KV, bs,
        hd]). The id list is padded to a power of two (pads gather the trash
        block) so XLA compiles O(log N) program variants, and the pad is
        sliced off."""
        if self._kv_extract is None:
            raise RuntimeError(
                "KV block transfer unsupported on a pipeline-parallel "
                "engine, and for a table that keeps a latent or a state")
        loop = asyncio.get_running_loop()
        n = len(block_ids)
        padded = np.zeros((_pow2_bucket(n),), np.int32)
        padded[:n] = block_ids

        def _ex():
            data = self._kv_extract(self.cache, padded)
            # quantized caches carry "ks"/"vs" scale planes alongside the
            # pages; slice the pad off every key uniformly
            # D2H is the point here: extract feeds the kvbm host tier /
            # the relay, off the step path
            data = jax.device_get(data)  # dynalint: disable=DT102
            return {key: np.asarray(arr)[:, :n]
                    for key, arr in data.items()}

        return await loop.run_in_executor(self._executor, _ex)

    async def inject_kv_blocks(
        self, block_ids, data: Dict[str, np.ndarray],
        *, seq_id: Optional[str] = None, epoch: Optional[int] = None,
    ) -> None:
        """Scatter per-block KV into physical blocks (pads scatter into the
        trash block, which absorbs garbage by design).

        With ``seq_id``/``epoch`` the reservation is re-validated *inside*
        the executor callable — immediately before the donated write — so a
        reservation recycled mid-flight is rejected, never scattered."""
        if self._kv_inject is None:
            raise RuntimeError("KV block transfer unsupported on a "
                               "pipeline-parallel engine")
        loop = asyncio.get_running_loop()
        n = len(block_ids)
        m = _pow2_bucket(n)
        padded = np.zeros((m,), np.int32)
        padded[:n] = block_ids
        if m != n:

            def _pad(a: np.ndarray) -> np.ndarray:
                pad_shape = list(a.shape)
                pad_shape[1] = m - n
                return np.concatenate(
                    [a, np.zeros(pad_shape, a.dtype)], axis=1
                )

            data = {key: _pad(a) for key, a in data.items()}

        def _in():
            if epoch is not None and not self.reservation_valid(seq_id, epoch):
                from ..disagg.ici import StaleEpochError

                raise StaleEpochError(
                    f"reservation {seq_id!r} epoch {epoch} is stale"
                )
            self.cache = self._kv_inject(self.cache, padded, data)

        await loop.run_in_executor(self._executor, _in)

    async def extract_kv(self, seq) -> Dict[str, np.ndarray]:
        """Gather a held sequence's KV blocks to host memory."""
        return await self.extract_kv_blocks(seq.block_table)

    async def inject_kv(self, seq, data: Dict[str, np.ndarray],
                        epoch: Optional[int] = None) -> None:
        """Scatter received KV into a reserved sequence's blocks."""
        await self.inject_kv_blocks(
            seq.block_table, data, seq_id=seq.seq_id, epoch=epoch
        )

    # ----------------------- embeddings (encode) -----------------------

    async def embed(self, token_ids_batch: List[List[int]]) -> List[List[float]]:
        """Encode-only step for ``/v1/embeddings``: mean-pooled, normalised
        final hidden states. Runs on the step executor thread (serialised
        with generation steps). Inputs are bucketed to powers of two so XLA
        compiles O(log T) encode programs."""
        if self._encode_fn is None:
            self._encode_fn = model_lib.make_encode_fn(
                self.model_config, None if self.pp > 1 else self.mesh,
                self.config.weight_dtype,
            )
        loop = asyncio.get_running_loop()

        for ids in token_ids_batch:
            if not ids:
                raise ValueError("empty embedding input")
            if len(ids) >= self.config.max_model_len:
                raise ValueError(
                    f"embedding input length {len(ids)} exceeds "
                    f"max_model_len {self.config.max_model_len}"
                )

        def _run() -> List[List[float]]:
            # group same-T-bucket inputs into one batched forward + one
            # device_get (the (B, T) buckets are both pow2, so compile
            # count stays O(log B * log T)); the step-executor thread is
            # shared with generation, so fewer dispatches = less decode
            # stall
            out: List[Optional[List[float]]] = [None] * len(token_ids_batch)
            groups: Dict[int, List[int]] = {}
            for i, ids in enumerate(token_ids_batch):
                groups.setdefault(_pow2_bucket(len(ids)), []).append(i)
            for T, idxs in groups.items():
                B = _pow2_bucket(len(idxs))
                tokens = np.zeros((B, T), np.int32)
                positions = np.full((B, T), -1, np.int32)
                for row, i in enumerate(idxs):
                    ids = token_ids_batch[i]
                    tokens[row, :len(ids)] = ids
                    positions[row, :len(ids)] = np.arange(len(ids))
                vecs = np.asarray(jax.device_get(
                    self._encode_fn(self.params, tokens, positions)
                ))
                for row, i in enumerate(idxs):
                    out[i] = vecs[row].tolist()
            return out  # type: ignore[return-value]

        return await loop.run_in_executor(self._executor, _run)

    async def embed_endpoint(self, request: Any, context: Context):
        """Wire adapter for the worker's ``embed`` endpoint."""
        vectors = await self.embed(
            [list(ids) for ids in request["token_ids_batch"]]
        )
        yield {"embeddings": vectors}

    def attach_kvbm(self, config=None, remote=None):
        """Enable the multi-tier block manager on this engine (optionally
        with a G4 remote tier)."""
        if self.pp > 1:
            raise RuntimeError("KVBM unsupported on a pipeline-parallel "
                               "engine (stacked cache has no transfer ops)")
        model_lib.refuse_unpaged(self.model_config, "KVBM")
        from ..kvbm.manager import KvbmConfig, KvbmManager

        self.kvbm = KvbmManager(self, config or KvbmConfig(), remote=remote)
        return self.kvbm

    # --------------------- device execution ----------------------------

    async def _execute_batch_async(self, batch) -> Tuple[List[int], List[int]]:
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._executor, self._execute_batch, batch
        )

    async def _dispatch_batch_async(self, batch):
        """Pipelined path: enqueue the batch's jitted calls on the dispatch
        thread (no sync), then hand the sampled-token handles to the
        batching fetcher. Returns the asyncio future of the results."""
        loop = asyncio.get_running_loop()
        handles = await loop.run_in_executor(
            self._executor, self._dispatch_batch, batch
        )
        self._fetcher.ensure_started()
        return self._fetcher.submit(loop, batch, handles)

    def _execute_batch(self, batch) -> Tuple[List[int], List[int]]:
        """Synchronous execution (pipeline_depth=1 / pp engines): dispatch
        then fetch in one executor turn."""
        if self.pp == 1:
            return self._fetch_results(batch, self._dispatch_batch(batch))
        prefill_samples: List[int] = []
        for chunk in batch.prefills:
            prefill_samples.append(self._run_prefill(chunk))
        decode_samples: List[List[int]] = []
        if batch.decodes:
            decode_samples = self._run_decode(batch)
        return prefill_samples, decode_samples

    def _dispatch_batch(self, batch):
        """Executor thread: build arrays + enqueue every jitted call for
        this window. NO host sync anywhere in here. Seat kills (finished,
        aborted, or preempted seqs whose blocks are recycling) flush FIRST
        so the in-order device queue applies them before any work that
        could touch reused blocks. Preempted slots are marked by the loop
        at schedule() time — a batch can be empty yet carry preemptions."""
        with _phase("engine.dispatch"):
            self._ap_flush_kills()
            obs_out = (
                batch.obs_records if self.obs is not None
                and hasattr(batch, "obs_records") else None
            )
            prefill_handles = []
            for c in batch.prefills:
                with _dispatch_phase("engine.dispatch.prefill", obs_out):
                    prefill_handles.append(self._dispatch_prefill(c, obs_out))
                if c.final and c.seq.t_first_token is None:
                    c.seq.t_dispatched = time.monotonic()
            decode_handle = None
            if batch.decode_rows:
                with _dispatch_phase("engine.dispatch.decode", obs_out):
                    decode_handle = self._dispatch_decode(
                        batch.decode_rows, obs_out)
        return prefill_handles, decode_handle

    def _ap_flush_kills(self) -> None:
        """Kill dead autopilot seats (one packed delta call). The dead-set
        swap is GIL-atomic against _ap_mark_dead calls from the event
        loop; anything added after the swap rides the next dispatch."""
        dead, self._ap_dead = self._ap_dead, set()
        if not dead:
            return
        deltas = {}
        for slot in dead:
            deltas[slot] = {
                "pos": 0, "vu": 0, "tk": 0, "seed": -1, "lt": -1,
                "table": (), "temp": 0.0, "tp": 1.0,
            }
            self._ap.pop(slot, None)
        self._ap_apply_deltas(deltas)

    def _count_fetch_sync(self) -> None:
        self.num_fetch_syncs += 1

    @hot_path
    def _fetch_results(self, batch, handles):
        """Fetch thread: device_get the window's sampled tokens (the only
        host↔device sync in the serving loop) and unpack per seat."""
        prefill_handles, decode_handle = handles
        to_get = list(prefill_handles)
        if decode_handle is not None:
            to_get.append(decode_handle[0])
        # designed sync point of the non-pipelined path: exactly one
        # device_get per executed batch, counted in num_fetch_syncs
        with _phase("engine.fetch"):
            got = jax.device_get(to_get) if to_get else []  # dynalint: disable=DT102
        if to_get:
            self.num_fetch_syncs += 1
        return self._unpack_results(batch, handles, got)

    @hot_path
    def _unpack_results(self, batch, handles, got):
        """Map fetched arrays back to per-seat sample lists. Decode sample
        columns follow the device seat map captured at dispatch, which may
        order (and pad) differently than the batch's row list."""
        t_got = time.monotonic()
        with _phase("engine.unpack"):
            return self._unpack(batch, handles, got, t_got)

    @hot_path
    def _unpack(self, batch, handles, got, t_got: float):
        batch.t_landed = t_got
        prefill_handles, decode_handle = handles
        prefill_samples = [
            int(np.asarray(g)[0]) for g in got[:len(prefill_handles)]
        ]
        for chunk in batch.prefills:
            if chunk.final and chunk.seq.t_first_token is None:
                chunk.seq.t_landed = t_got
        decode_samples: List[List[int]] = []
        moe_stats = None
        if decode_handle is not None:
            col_of = {}
            for col, slot in enumerate(decode_handle[1]):
                col_of.setdefault(slot, col)
            out = np.asarray(got[-1])  # [1, B] (spec: [k+3, B] packed)
            if len(decode_handle) > 2 and decode_handle[2]:
                decode_samples = self._unpack_spec(batch, out, col_of)
            else:
                if self._moe_stats:
                    # the routing counters' row behind the sample row
                    out, moe_stats = out[:-1], out[-1]
                for row in batch.decode_rows:
                    col = col_of[row.slot]
                    decode_samples.append([
                        int(out[k, col])
                        for k in range(min(row.accepted, out.shape[0]))
                    ])
        if self.obs is not None:
            self._obs_on_land(batch, decode_samples, t_got, moe_stats)
        return prefill_samples, decode_samples

    @hot_path
    def _obs_on_land(self, batch, decode_samples, t_got: float,
                     moe_stats=None) -> None:
        """Stamp landing time + realized goodput on this window's records
        and commit them to the flight recorder. Runs right after the
        window's one designed device_get, on already-fetched host ints —
        no extra syncs. The batch's host time (the loop's ``host_s``, this
        thread's ``unpack_s`` since the get returned) goes on its decode
        record, or its last record when it has none."""
        recs = getattr(batch, "obs_records", None)
        if not recs:
            return
        t_land = time.monotonic()
        emitted = sum(len(w) for w in decode_samples)
        owner = next((r for r in recs if r.kind != PREFILL), recs[-1])
        owner.host_s = batch.host_s
        owner.loop_busy_s = batch.loop_busy_s
        owner.loop_cpu_s = batch.loop_cpu_s
        owner.unpack_s = t_land - t_got
        if moe_stats is not None and owner.kind == DECODE:
            for name, v in zip(MOE_STATS, moe_stats):
                setattr(owner, name, int(v))
        for rec in recs:
            rec.t_land = t_land
            if rec.kind != PREFILL:
                rec.goodput_tokens = emitted
            self.obs.commit(rec)
        recs.clear()

    @hot_path
    def _unpack_spec(self, batch, out, col_of) -> List[List[int]]:
        """Spec verify window landing: packed rows 0..k are emitted token
        candidates, row k+1 n_emitted, row k+2 n_drafted. Runs on the
        (single) executor thread — spec forces the synchronous loop — so
        correcting the host mirror's pessimistic pos here is ordered
        strictly before the next dispatch."""
        kk = self._spec_k
        stats = self.spec_stats
        decode_samples: List[List[int]] = []
        win_drafted = win_accepted = 0
        for row in batch.decode_rows:
            col = col_of[row.slot]
            n = int(out[kk + 1, col])
            ndraft = int(out[kk + 2, col])
            n_use = min(n, row.accepted)
            decode_samples.append([int(out[j, col]) for j in range(n_use)])
            row.seq.spec_drafted += ndraft
            row.seq.spec_accepted += max(n - 1, 0)
            win_drafted += ndraft
            win_accepted += max(n - 1, 0)
            stats.drafted += ndraft
            stats.accepted += max(n - 1, 0)
            stats.emitted += n_use
            st = self._ap.get(row.slot)
            if st is not None and st["seq_id"] == row.seq.seq_id:
                st["pos"] = row.base + n
        stats.windows += 1
        if self.obs is not None:
            for rec in getattr(batch, "obs_records", ()):
                if rec.kind == SPEC_VERIFY:
                    rec.spec_drafted += win_drafted
                    rec.spec_accepted += win_accepted
        th = self.config.spec_auto_disable_threshold
        if (th > 0.0 and not self._spec_auto_disabled
                and stats.drafted >= self.config.spec_auto_disable_window
                and stats.acceptance_rate < th):
            # one-way: drafting is costing more verify compute than it
            # saves in syncs on this workload; fall back to plain windows
            self._spec_auto_disabled = True
            self.scheduler.spec_plan_window = None
            log.info(
                "spec decode auto-disabled: acceptance %.3f < %.3f after "
                "%d drafts", stats.acceptance_rate, th, stats.drafted,
            )
        return decode_samples

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _bucket_for(self, kind: str, n: int) -> int:
        """Bucket ``n`` on the config's grid for ``kind``.
        Stall-quarantined buckets route to the next rung up — a different
        compiled program doing the same work with padding."""
        cfg = self.config
        grid = (cfg.decode_buckets if kind == "decode"
                else cfg.prefill_buckets)
        b = _bucket(n, grid)
        if self._shape_quarantine and (kind, b) in self._shape_quarantine:
            for g in grid:
                if g >= b and (kind, g) not in self._shape_quarantine:
                    return g
        return b

    def _shape_bucket(self, kind: str, n: int) -> int:
        return self._bucket_for(kind, n)

    def _quarantine_shape(self, cls) -> None:
        """When the LARGEST decode bucket wedges there is no rung to route
        to — rebuild the decode window on the einsum attention impl instead
        (a different program for the same shape class), once."""
        kind, bucket = cls
        if (self.pp == 1 and kind == "decode"
                and not self._stall_einsum_fallback):
            cfg = self.config
            if bucket >= max(cfg.decode_buckets):
                try:
                    import dataclasses as _dc
                    fb_cfg = _dc.replace(cfg, attention_impl="einsum")
                    self._ap_window_fn, self._ap_delta_fn = (
                        model_lib.make_autopilot_fns(
                            self.model_config, fb_cfg, self._ap_Wcap,
                            self.mesh,
                        )
                    )
                    self._stall_einsum_fallback = True
                    self._decode_kv_tile = 0
                    self.attention_impl_choice = model_lib.attention_choice(
                        self.model_config, fb_cfg, self.mesh)
                    log.warning(
                        "stall watchdog: decode:%d is the largest rung — "
                        "rebuilt the decode window on the einsum attention "
                        "impl instead of quarantining it", bucket,
                    )
                    return
                except Exception:
                    log.exception(
                        "einsum fallback rebuild failed — quarantining "
                        "decode:%d (it will keep dispatching at its own "
                        "rung)", bucket,
                    )
        super()._quarantine_shape(cls)

    def _pause_spec(self) -> None:
        # pp engines never set _spec_k (spec decode is single-engine only)
        if getattr(self, "_spec_k", 0) <= 0 or self._spec_auto_disabled:
            return
        self._pressure_spec_saved = self.scheduler.spec_plan_window
        self.scheduler.spec_plan_window = None
        log.warning("pressure ladder: speculative decoding paused")

    def _resume_spec(self) -> None:
        if self._pressure_spec_saved is None:
            return
        if not self._spec_auto_disabled:
            self.scheduler.spec_plan_window = self._pressure_spec_saved
        self._pressure_spec_saved = None
        log.info("pressure ladder: speculative decoding resumed")

    def _prefill_arrays(self, chunk: PrefillChunk, use_sp: bool):
        cfg = self.config
        seq = chunk.seq
        if chunk.length <= max(cfg.prefill_buckets) and not use_sp:
            T = self._bucket_for("prefill", chunk.length)
        else:
            # sp full-prompt chunks (and any oversized chunk) bucket to the
            # next power of two — always divisible by the sp ring size
            T = _pow2_bucket(chunk.length)
        # only the blocks this chunk can touch: keeps W a function of the
        # chunk shape alone, so lookahead-grown tables don't mint new
        # (T, W) programs mid-serving (remote compiles are ~50 s)
        bs = cfg.block_size
        nb = min((chunk.start + chunk.length + bs - 1) // bs,
                 len(seq.block_table))
        W = self._prefill_table_width(nb)
        tokens = np.zeros((1, T), np.int32)
        positions = np.full((1, T), -1, np.int32)
        all_toks = seq.all_tokens()
        tokens[0, :chunk.length] = all_toks[
            chunk.start:chunk.start + chunk.length
        ]
        positions[0, :chunk.length] = np.arange(
            chunk.start, chunk.start + chunk.length
        )
        model_lib.check_feed_positions(positions)
        tables = np.zeros((1, W), np.int32)
        tables[0, :nb] = seq.block_table[:nb]
        return {
            "tokens": tokens, "positions": positions, "tables": tables,
            "last_idx": np.array([chunk.length - 1], np.int32),
            "temp": np.array([seq.temperature], np.float32),
            "top_k": np.array([seq.top_k], np.int32),
            "top_p": np.array([seq.top_p], np.float32),
            "seeds": np.array([seq.seed], np.int32),
        }

    def _mm_chunk_rows(self, chunk: PrefillChunk):
        """(chunk-relative row, embedding index) of multimodal placeholder
        positions inside this chunk (decode never needs this — placeholders
        live in the prompt only)."""
        seq = chunk.seq
        if not seq.mm_positions:
            return []
        lo, hi = chunk.start, chunk.start + chunk.length
        return [
            (p - lo, k) for k, p in enumerate(seq.mm_positions)
            if lo <= p < hi
        ]

    @hot_path
    def _dispatch_prefill(self, chunk: PrefillChunk, obs_out=None):
        """Enqueue one prefill chunk on the ring path; returns the sampled
        handle [1] (garbage unless ``chunk.final``). No host sync."""
        cfg = self.config
        seq = chunk.seq
        self.num_prefill_dispatches += 1
        use_sp = (
            self._sp_prefill_fn is not None
            and chunk.start == 0 and chunk.final
            and chunk.length >= cfg.sp_prefill_threshold
            and not seq.mm_positions  # the sp path has no mm splicing
        )
        a = self._prefill_arrays(chunk, use_sp)
        if obs_out is not None:
            # host-known ints only — prompt tokens are goodput at dispatch;
            # context_sum = Σ attended context over the chunk's positions
            L, S = chunk.length, chunk.start
            ctx = L * S + L * (L + 1) // 2
            T = a["tokens"].shape[1]
            keys = a["tables"].shape[1] * cfg.block_size if self._latent \
                else 0
            obs_out.append(StepRecord(
                kind=PREFILL, t_dispatch=time.monotonic(),
                bucket=T,
                rows=1, live_rows=1,
                padded_tokens=T, real_tokens=L,
                goodput_tokens=L,
                context_sum=ctx,
                kv_pages_written=kv_pages_written(
                    [(S, L)], block_size=cfg.block_size),
                state_rows=int(self._seat_state),
                latent_context_sum=ctx if self._latent else 0,
                latent_keys_walked=keys and latent_keys_walked(
                    S, L, model_lib.latent_chunk_tiles(self.mesh, T, keys),
                    keys),
                latent_keys_gathered=keys,
            ))
        slot = np.array(
            [seq.slot if seq.slot >= 0 else cfg.max_num_seqs], np.int32
        )
        write = np.array([1 if chunk.final else 0], np.int32)
        mm_rows = self._mm_chunk_rows(chunk)
        if mm_rows:
            if self._mm_ring_fn is None:
                self._mm_ring_fn = model_lib.make_mm_ring_prefill_fn(
                    self.model_config, cfg, self.mesh
                )
            D = self.model_config.hidden_size
            T = a["tokens"].shape[1]
            mm_embeds = np.zeros((1, T, D), np.float32)
            mm_mask = np.zeros((1, T), bool)
            emb = np.asarray(seq.mm_embeddings, np.float32)
            for row, k in mm_rows:
                mm_embeds[0, row] = emb[k]
                mm_mask[0, row] = True
            self.num_mm_prefills += 1
            if self.step_sink is not None:
                self.step_sink("mrp", {
                    **a, "slot": slot, "write": write,
                    "mm_embeds": mm_embeds,
                    "mm_mask": mm_mask.astype(np.int32),
                })
            self.cache, new_lt, sampled = self._mm_ring_fn(
                self.params, self.cache, self._ctl["last_tok"],
                a["tokens"], a["positions"], a["tables"], a["last_idx"],
                slot, write, self._next_rng(), a["temp"], a["top_k"],
                a["top_p"], a["seeds"], mm_embeds, mm_mask,
            )
            self._ctl = {**self._ctl, "last_tok": new_lt}
            return sampled
        if use_sp:
            if self.step_sink is not None:
                self.step_sink("rsp", {**a, "slot": slot, "write": write})
            self.num_sp_prefills += 1
            self.cache, new_lt, sampled = self._sp_prefill_fn(
                self.params, self.cache, self._ctl["last_tok"],
                a["tokens"], a["positions"], a["tables"], a["last_idx"],
                slot, write, self._next_rng(), a["temp"], a["top_k"],
                a["top_p"], a["seeds"],
            )
            self._ctl = {**self._ctl, "last_tok": new_lt}
            return sampled
        # plain path: pack every int input into ONE upload (2 total with
        # the f32 pair) — prefill uploads dominate the serial channel
        T = a["tokens"].shape[1]
        W = a["tables"].shape[1]
        fn = self._packed_prefill_fns.get((T, W))
        if fn is None:
            fn = model_lib.make_packed_prefill_fn(
                self.model_config, cfg, T, W, self.mesh
            )
            self._packed_prefill_fns[(T, W)] = fn
        pint = np.zeros((1, T + W + model_lib.PP_SCALARS), np.int32)
        pint[0, :T] = a["tokens"][0]
        pint[0, T:T + W] = a["tables"][0]
        pint[0, T + W:] = (
            chunk.length, chunk.start, int(slot[0]), int(write[0]),
            seq.top_k, seq.seed,
            int(round(seq.temperature * model_lib.PP_QUANT)),
            int(round(seq.top_p * model_lib.PP_QUANT)),
        )
        if self.step_sink is not None:
            self.step_sink("pp", {"pint": pint,
                                  "tw": np.array([T, W], np.int32)})
        self.cache, new_lt, sampled = fn(
            self.params, self.cache, self._ctl["last_tok"], pint,
            self._next_rng(),
        )
        self._ctl = {**self._ctl, "last_tok": new_lt}
        return sampled

    @hot_path
    def _ap_apply_deltas(self, deltas: Dict[int, Dict[str, Any]]) -> None:
        """Pack + enqueue one control-state delta call (2 uploads total —
        per-field arrays cost one upload each; ~15 ms apiece when measured
        on an earlier transport; re-measured by chip_smoke.py, see
        CHANGES)."""
        Wcap = self._ap_Wcap
        n = _pow2_bucket(len(deltas))
        trash = self.config.max_num_seqs
        di = np.zeros((n, model_lib.CTL_I32_FIELDS + Wcap), np.int32)
        di[:, 0] = trash               # pad rows scatter to the trash slot
        di[:, 5] = -1                  # pad rows keep last_tok
        df = np.zeros((n, 2), np.float32)
        for i, (slot, d) in enumerate(sorted(deltas.items())):
            di[i, 0] = slot
            di[i, 1] = d["pos"]
            di[i, 2] = d["vu"]
            di[i, 3] = d["tk"]
            di[i, 4] = d["seed"]
            di[i, 5] = d["lt"]
            table = d["table"]
            di[i, 6:6 + len(table)] = table
            df[i, 0] = d["temp"]
            df[i, 1] = d["tp"]
        if self.step_sink is not None:
            self.step_sink("ctl", {"di": di, "df": df})
        self._ctl = self._ap_delta_fn(self._ctl, di, df)

    @hot_path
    def _dispatch_decode(self, rows, obs_out=None):
        """Enqueue one autopilot decode window. Steady state (same seats,
        no growth) dispatches with ZERO fresh host arrays — all control
        state is device-resident; the host sends packed deltas only on
        joins, block growth, resumes, and seat-map changes. Returns
        (samples_handle [1, B], col_map, spec) where col_map[device
        column] is the slot computed there and ``spec`` marks a packed
        spec-window handle."""
        cfg = self.config
        bs = cfg.block_size
        spec = self._spec_active()
        # spec windows land a data-dependent 1..k+1 tokens; mirror the
        # device's advance pessimistically here (max) and correct it in
        # _unpack_spec before the next dispatch (synchronous loop)
        K = (self._spec_k + 1) if spec else 1
        deltas: Dict[int, Dict[str, Any]] = {}
        reset_rows: List[Any] = []
        for r in rows:
            s = r.seq
            vu = min(len(s.block_table) * bs, cfg.max_model_len)
            tlen = len(s.block_table)
            params_key = (s.temperature, s.top_k, s.top_p, s.seed)
            st = self._ap.get(r.slot)
            if (st is None or st["seq_id"] != s.seq_id
                    or st["pos"] != r.base or st["params"] != params_key):
                # join / resume / drift: reset the whole slot. lt = -1
                # keeps the ring token the producer wrote on device; a
                # host-known token (resume, inject) is pushed instead.
                deltas[r.slot] = {
                    "pos": r.base, "vu": vu, "tk": s.top_k,
                    "seed": s.seed,
                    "lt": -1 if r.tok_src else r.tok_host,
                    "table": s.block_table, "temp": s.temperature,
                    "tp": s.top_p,
                }
                reset_rows.append(r)
            elif st["vu"] != vu or st["tlen"] != tlen:
                deltas[r.slot] = {
                    "pos": r.base, "vu": vu, "tk": s.top_k,
                    "seed": s.seed, "lt": -1,
                    "table": s.block_table, "temp": s.temperature,
                    "tp": s.top_p,
                }
            # mirror the device's own advance: acc = clip(vu - pos, 0, K)
            self._ap[r.slot] = {
                "seq_id": s.seq_id, "params": params_key,
                "pos": r.base + min(max(vu - r.base, 0), K),
                "vu": vu, "tlen": tlen,
            }
        if deltas:
            self._ap_apply_deltas(deltas)
            if spec and reset_rows:
                self._spec_fill_hist(reset_rows)
        # seat map: reuse the device map only when the LIVE seats it holds
        # are exactly the scheduled set. Dead seats idle at vu=0, but a
        # LIVE slot the scheduler skipped this round (pool pressure) must
        # not keep its column — the window would advance its device pos/ring
        # token behind the host mirror's back. Rebuild + upload
        # excludes it; its device state is untouched until re-scheduled.
        needed = [r.slot for r in rows]
        B = self._bucket_for("decode", len(needed))
        live = {s for s in self._ap_cols if s in self._ap}
        if (self._ap_rows_dev is None or len(self._ap_cols) != B
                or live != set(needed)):
            trash = cfg.max_num_seqs
            cols = list(needed) + [trash] * (B - len(needed))
            arr = np.asarray(cols, np.int32)
            if self.step_sink is not None:
                self.step_sink("cols", {"rows": arr})
            self._ap_cols = cols
            self._ap_rows_dev = jax.device_put(arr)
        if self.step_sink is not None:
            self.step_sink("sw" if spec else "w", {})
        if obs_out is not None:
            # realized goodput (emitted tokens; spec accept counts) is
            # stamped at landing — only padded/real shapes are known here
            ctx = sum(K * r.base + K * (K + 1) // 2 for r in rows)
            walked = walked_w = ctx_w = 0
            if not spec:
                # a plain window is one step, attending base + 1 positions
                attended = [r.base + 1 for r in rows]
                tile = self._decode_kv_tile
                # the einsum path (no tile) gathers every column
                walked = kv_blocks_walked(
                    attended, kv_tile=tile, block_size=bs,
                ) if tile else B * self._ap_Wcap
                win = self._attn_window
                if win:
                    ctx_w = sum(min(n, win) for n in attended)
                    walked_w = kv_blocks_walked(
                        attended, kv_tile=tile, block_size=bs, window=win,
                    ) if tile else walked
            obs_out.append(StepRecord(
                kind=SPEC_VERIFY if spec else DECODE,
                t_dispatch=time.monotonic(),
                bucket=B,
                rows=B, live_rows=len(rows),
                padded_tokens=B * K, real_tokens=len(rows) * K,
                context_sum=ctx, kv_blocks_walked=walked,
                kv_blocks_walked_window=walked_w,
                context_sum_window=ctx_w,
                # a spec window writes a row's K fed positions at once, a
                # decode window one page a row
                kv_pages_written=kv_pages_written(
                    [(r.base, K) for r in rows], block_size=bs,
                ) if spec else len(rows),
                state_rows=len(rows) * K if self._seat_state else 0,
                latent_context_sum=ctx if self._latent else 0,
            ))
        fn = self._spec_window_fn if spec else self._ap_window_fn
        self.cache, self._ctl, samples = fn(
            self.params, self.cache, self._ctl, self._ap_rows_dev,
        )
        return samples, list(self._ap_cols), spec

    def _spec_active(self) -> bool:
        return (self._spec_k > 0 and not self._spec_auto_disabled
                and not self._pressure_spec_paused)

    def _spec_fill_hist(self, rows) -> None:
        """Inject full token histories for joining/reset seats so the
        on-device drafter has context immediately — including resumed and
        migrated sequences, whose carried tokens arrive with the request.
        One [n, Hcap+1] upload per join delta; steady-state windows extend
        the history on device with no uploads at all."""
        Hcap = self._spec_hist_cap
        trash = self.config.max_num_seqs
        n = _pow2_bucket(len(rows))
        slots = np.full((n,), trash, np.int32)
        hrows = np.full((n, Hcap + 1), -1, np.int32)
        for i, r in enumerate(rows):
            toks = r.seq.all_tokens()[:min(r.base + 1, Hcap)]
            slots[i] = r.slot
            hrows[i, :len(toks)] = toks
        if self.step_sink is not None:
            self.step_sink("sph", {"slots": slots, "hist": hrows})
        self._ctl = self._spec_hist_fill_fn(self._ctl, slots, hrows)

    # ---- legacy synchronous path (pipeline-parallel engines only) ----

    def _run_prefill(self, chunk: PrefillChunk) -> int:
        a = self._prefill_arrays(chunk, use_sp=False)
        mm_rows = self._mm_chunk_rows(chunk)
        if mm_rows:
            if self._mm_prefill_fn is None:
                self._mm_prefill_fn = model_lib.make_mm_prefill_fn(
                    self.model_config, self.config, self.mesh
                )
            D = self.model_config.hidden_size
            T = a["tokens"].shape[1]
            mm_embeds = np.zeros((1, T, D), np.float32)
            mm_mask = np.zeros((1, T), bool)
            emb = np.asarray(chunk.seq.mm_embeddings, np.float32)
            for row, k in mm_rows:
                mm_embeds[0, row] = emb[k]
                mm_mask[0, row] = True
            self.num_mm_prefills += 1
            self.cache, sampled = self._mm_prefill_fn(
                self.params, self.cache, a["tokens"], a["positions"],
                a["tables"], a["last_idx"], self._next_rng(), a["temp"],
                a["top_k"], a["top_p"], a["seeds"], mm_embeds, mm_mask,
            )
            # sync fallback path (no batching fetcher): one pull per step
            return int(np.asarray(jax.device_get(sampled))[0])  # dynalint: disable=DT101,DT102
        if self.step_sink is not None:
            self.step_sink("p", {**a})
        self.cache, sampled = self._step_fn(
            self.params, self.cache, a["tokens"], a["positions"],
            a["tables"], a["last_idx"], self._next_rng(), a["temp"],
            a["top_k"], a["top_p"], a["seeds"],
        )
        # sync fallback path (no batching fetcher): one pull per step
        return int(np.asarray(jax.device_get(sampled))[0])  # dynalint: disable=DT101,DT102

    def _run_decode(self, batch) -> List[List[int]]:
        cfg = self.config
        rows = batch.decode_rows
        B = self._bucket_for("decode", len(rows))
        W = _pow2_bucket(
            max(len(r.seq.block_table) for r in rows),
            cfg.max_blocks_per_seq,
        )
        tokens = np.zeros((B, 1), np.int32)
        positions = np.full((B, 1), -1, np.int32)
        tables = np.zeros((B, W), np.int32)
        temp = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        seeds = np.full((B,), -1, np.int32)
        for i, r in enumerate(rows):
            s = r.seq
            tokens[i, 0] = r.tok_host
            positions[i, 0] = r.base
            tables[i, :len(s.block_table)] = s.block_table
            temp[i] = s.temperature
            top_k[i] = s.top_k
            top_p[i] = s.top_p
            seeds[i] = s.seed
        last_idx = np.zeros((B,), np.int32)
        if self.step_sink is not None:
            self.step_sink("d", {
                "tokens": tokens, "positions": positions, "tables": tables,
                "last_idx": last_idx, "temp": temp, "top_k": top_k,
                "top_p": top_p, "seeds": seeds,
            })
        self.cache, sampled = self._step_fn(
            self.params, self.cache, tokens, positions, tables,
            last_idx, self._next_rng(), temp, top_k, top_p, seeds,
        )
        # sync fallback path (no batching fetcher): one pull per step
        out = np.asarray(jax.device_get(sampled))  # dynalint: disable=DT102
        return [[int(out[i])] for i in range(len(rows))]
