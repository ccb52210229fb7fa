"""XLA compile + involuntary-remat watchdog.

Counts backend compilations per jitted function after warmup via the
``jax.monitoring`` duration-event stream and parses ``[SPMD] Involuntary
full rematerialization`` warnings into structured counters — the gate
ROADMAP item 2 (multichip) needs before it can claim a clean steady state.

Attribution works because ``/jax/core/compile/backend_compile_duration``
fires *synchronously on the compiling thread*, exactly once per real
backend compile (cache hits fire nothing). Each
jitted function the engine builds is wrapped by :func:`label`, which sets
a thread-local tag around the call; a compile event observed inside a
labelled call is attributed to that function, anything else lands in the
``<unattributed>`` bucket (e.g. incidental ``jnp`` helper compiles).

Steady-state discipline: after :func:`mark_warmup_done` every further
compile increments the *steady* counters — the thing that must stay flat
in serving. ``engine_recompiles_total{fn}`` / ``engine_involuntary_remats_
total`` surface through the worker gauges and ``obs_snapshot()``'s
``recompiles_steady_state``.
"""

from __future__ import annotations

import logging
import os
import re
import sys
import tempfile
import threading
from contextlib import contextmanager
from typing import Dict, Optional

from ..utils.hotpath import hot_path

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
UNATTRIBUTED = "<unattributed>"

# XLA's SPMD partitioner emits this (C++ warning text, also seen via log
# capture) when it must rematerialize a full tensor because no valid
# sharding propagation exists — the multichip perf killer ROADMAP item 2
# tracks. Matched case-insensitively and tolerant of prefix noise.
REMAT_RE = re.compile(
    r"\[SPMD\]\s+Involuntary full rematerialization", re.IGNORECASE
)


class CompileWatch:
    """Process-wide compile/remat counters (jax.monitoring has no
    unregister, so one listener lives for the process; tests drive the
    singleton through :func:`reset`)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._installed = False
        self._steady = False
        self.compiles_total: Dict[str, int] = {}
        self.compiles_steady: Dict[str, int] = {}
        self.compile_secs: Dict[str, float] = {}
        self.remats_total = 0
        self.remats_steady = 0

    # --------------------------- listener ------------------------------

    def install(self) -> None:
        """Idempotently register the jax.monitoring listener (lazy jax
        import: non-device processes pay nothing)."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event != COMPILE_EVENT:
            return
        fn = getattr(self._tls, "label", None) or UNATTRIBUTED
        with self._lock:
            self.compiles_total[fn] = self.compiles_total.get(fn, 0) + 1
            self.compile_secs[fn] = (
                self.compile_secs.get(fn, 0.0) + float(duration))
            if self._steady:
                self.compiles_steady[fn] = (
                    self.compiles_steady.get(fn, 0) + 1)

    # -------------------------- attribution ----------------------------

    def label(self, fn, name: str):
        """Wrap a jitted callable so compiles during its calls attribute
        to ``name``. Nesting-safe (inner label wins, outer restored)."""
        tls = self._tls

        @hot_path
        def labelled(*args, **kwargs):
            prev = getattr(tls, "label", None)
            tls.label = name
            try:
                return fn(*args, **kwargs)
            finally:
                tls.label = prev

        labelled.__wrapped__ = fn
        labelled.__compile_label__ = name
        return labelled

    # ------------------------- remat parsing ----------------------------

    def note_remat(self, n: int = 1) -> None:
        with self._lock:
            self.remats_total += n
            if self._steady:
                self.remats_steady += n

    def scan_log_text(self, text: str) -> int:
        """Count involuntary-remat warnings in captured log/stderr text
        and fold them into the counters. Returns the number found."""
        n = len(REMAT_RE.findall(text or ""))
        if n:
            self.note_remat(n)
        return n

    # --------------------------- lifecycle ------------------------------

    def mark_warmup_done(self) -> None:
        """Enter steady state: compiles from here on are *recompiles*."""
        with self._lock:
            self._steady = True
            self.compiles_steady = {}
            self.remats_steady = 0

    def reset(self) -> None:
        """Back to warmup with zeroed counters (test isolation)."""
        with self._lock:
            self._steady = False
            self.compiles_total = {}
            self.compiles_steady = {}
            self.compile_secs = {}
            self.remats_total = 0
            self.remats_steady = 0

    # --------------------------- snapshots ------------------------------

    def steady_by_label(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.compiles_steady)

    def steady_total(self) -> int:
        with self._lock:
            return sum(self.compiles_steady.values())

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "recompiles_steady_state": sum(self.compiles_steady.values()),
                "recompiles_by_fn": dict(self.compiles_steady),
                "compiles_total": sum(self.compiles_total.values()),
                "compiles_by_fn": dict(self.compiles_total),
                "compile_secs_by_fn": dict(self.compile_secs),
                "involuntary_remats_total": self.remats_total,
                "involuntary_remats_steady": self.remats_steady,
                "steady": self._steady,
            }


class RematLogHandler(logging.Handler):
    """Folds involuntary-remat warnings that reach Python logging (jax /
    absl bridges) into the watch's counters."""

    def __init__(self, watch: "CompileWatch"):
        super().__init__(level=logging.WARNING)
        self._watch = watch

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self._watch.scan_log_text(record.getMessage())
        except Exception:  # a counter must never break logging
            pass


# ------------------------- module-level singleton --------------------------

_watch = CompileWatch()
_remat_handler: Optional[RematLogHandler] = None


def get_watch() -> CompileWatch:
    return _watch


def install() -> None:
    """Register the compile listener + the remat log handler (idempotent)."""
    global _remat_handler
    _watch.install()
    if _remat_handler is None:
        _remat_handler = RematLogHandler(_watch)
        for name in ("jax", "absl"):  # children propagate up to these
            logging.getLogger(name).addHandler(_remat_handler)


def label(fn, name: str):
    return _watch.label(fn, name)


def mark_warmup_done() -> None:
    _watch.mark_warmup_done()


def scan_log_text(text: str) -> int:
    return _watch.scan_log_text(text)


def snapshot() -> dict:
    return _watch.snapshot()


def steady_total() -> int:
    return _watch.steady_total()


def steady_by_label() -> Dict[str, int]:
    return _watch.steady_by_label()


class _StderrCapture:
    """Handle returned by :func:`capture_stderr`: ``.text()`` is everything
    written to fd 2 inside the block (so far, or in total after exit)."""

    def __init__(self, path: str):
        self._path = path
        self._final: Optional[str] = None

    def _freeze(self) -> None:
        self._final = self.text()

    def text(self) -> str:
        if self._final is not None:
            return self._final
        sys.stderr.flush()
        try:
            with open(self._path, "r", errors="replace") as f:
                return f.read()
        except OSError:
            return ""


@contextmanager
def capture_stderr():
    """Tee-free fd-level stderr capture.

    XLA's ``[SPMD] Involuntary full rematerialization`` warnings are
    emitted by C++ absl logging straight to file descriptor 2 — they never
    pass through Python's ``sys.stderr`` or the logging bridge, so a
    ``redirect_stderr`` misses them. This swaps fd 2 for a temp file via
    ``os.dup2`` for the duration of the block and yields a handle whose
    ``.text()`` can be fed to :func:`scan_log_text`.
    """
    sys.stderr.flush()
    saved_fd = os.dup(2)
    tmp = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".stderr", delete=False
    )
    cap = _StderrCapture(tmp.name)
    try:
        os.dup2(tmp.fileno(), 2)
        yield cap
    finally:
        sys.stderr.flush()
        os.dup2(saved_fd, 2)
        os.close(saved_fd)
        cap._freeze()
        tmp.close()
        # replay the captured bytes onto the real stderr so the capture
        # is observability, not a muzzle
        text = cap.text()
        if text:
            sys.stderr.write(text)
            sys.stderr.flush()
        try:
            os.unlink(tmp.name)
        except OSError:
            pass


@contextmanager
def assert_no_recompiles(allow: int = 0):
    """Test helper: fail if more than ``allow`` steady-state compiles (any
    label) happen inside the block. Enters steady state if not already."""
    if not _watch.snapshot()["steady"]:
        _watch.mark_warmup_done()
    before = _watch.steady_by_label()
    yield _watch
    after = _watch.steady_by_label()
    delta = {
        k: after.get(k, 0) - before.get(k, 0)
        for k in set(before) | set(after)
        if after.get(k, 0) != before.get(k, 0)
    }
    total = sum(delta.values())
    if total > allow:
        raise AssertionError(
            f"unexpected steady-state XLA recompiles: {delta!r} "
            f"({total} > allowed {allow})"
        )
