"""On-demand ``jax.profiler`` capture behind ``/debug/profile?ms=N``.

One capture at a time per process (the profiler is a global); traces land
in a fresh TensorBoard-loadable directory under the configured base dir
(``DYNTPU_OBS_PROFILE_DIR``, default the system temp dir). CPU-safe: the
JAX profiler produces a (host-only) trace without an accelerator, which
is what the smoke test exercises.
"""

from __future__ import annotations

import asyncio
import os
import tempfile
import threading
import time
from typing import Optional

from ..utils.logging import get_logger

log = get_logger("observability.profile")

DEFAULT_MS = 1000
MAX_MS = 30_000

_capture_lock = threading.Lock()  # one capture per process, ever
_last: Optional[dict] = None      # the newest finished capture


def last_capture() -> Optional[dict]:
    """``{trace_dir, captured_ms, t0_mono, t0_unix}`` of this process's
    newest capture (None before the first): the engine probe on ``/health``
    reports it as ``last_profile``, so a reader finds the capture and can
    lay spans and step records (``time.monotonic()``) on its clock."""
    return dict(_last) if _last else None


def default_base_dir() -> str:
    return os.environ.get(
        "DYNTPU_OBS_PROFILE_DIR",
        os.path.join(tempfile.gettempdir(), "dyntpu-profiles"),
    )


class ProfileBusyError(RuntimeError):
    """A capture is already running in this process."""


async def capture(ms: int, base_dir: str = "", python: bool = False) -> dict:
    """Capture a ``ms``-millisecond profiler trace; returns metadata
    (``trace_dir`` is TensorBoard-loadable:
    ``tensorboard --logdir <trace_dir>``). Raises :class:`ProfileBusyError`
    when a capture is already in flight.

    The Python tracer is off unless ``python``: it hooks every Python call
    of every thread for the whole capture, which slows the host enough to
    change what is being profiled, and the host planes carry the engine's
    own phases (``engine.PHASES``) without it."""
    global _last
    ms = max(1, min(int(ms), MAX_MS))
    base = base_dir or default_base_dir()
    if not _capture_lock.acquire(blocking=False):
        raise ProfileBusyError("a profile capture is already running")
    try:
        os.makedirs(base, exist_ok=True)
        trace_dir = tempfile.mkdtemp(
            prefix=time.strftime("trace-%Y%m%d-%H%M%S-"), dir=base
        )
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1 if python else 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0, t0_unix = time.monotonic(), time.time()
        try:
            # DT301: the wait must yield the event loop — the engine keeps
            # serving (that's the point: profile it under load)
            await asyncio.sleep(ms / 1000.0)
        finally:
            # collecting and writing the capture takes seconds on a busy
            # chip (4.8 s of a 3 s request seen): off the event loop, in a
            # thread, so the engine is not stalled by its own profile
            await asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)
        wall_ms = (time.monotonic() - t0) * 1000.0
    finally:
        _capture_lock.release()
    log.info("profiler trace captured to %s (%.0f ms)", trace_dir, wall_ms)
    _last = {
        "trace_dir": trace_dir,
        "captured_ms": round(wall_ms, 1),
        "t0_mono": t0,
        "t0_unix": t0_unix,
    }
    return {
        **_last,
        "requested_ms": ms,
        "tensorboard": f"tensorboard --logdir {trace_dir}",
    }
