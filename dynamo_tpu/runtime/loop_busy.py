"""How busy a process's event loop is: seconds spent running callbacks as
against waiting in its selector.

Store, frontend and worker are each ONE asyncio loop; every stream's frames,
every watch and the engine's own step task share it. ``install()`` wraps the
running loop's ``selector.select`` once: the time inside a select that was
allowed to block is the loop's idle time, everything else between install
and now is busy (callbacks, the loop's own bookkeeping, zero-timeout polls
taken while work is queued, and waits for the interpreter lock held by
another thread: the loop could not run then either). One counter a loop;
readers take deltas of ``busy_s()``.

A loop without a ``_selector`` (uvloop, a proactor) gets no counter:
``install`` returns None and whoever asked exports nothing.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

_ATTR = "_dyntpu_loop_busy"


class LoopBusyCounter:
    def __init__(self):
        self.t_start = time.monotonic()
        self.wait_s = 0.0

    def busy_s(self, now: Optional[float] = None) -> float:
        """Seconds the loop was not waiting between the counter's install
        and ``now`` (a ``time.monotonic()`` the caller has already read:
        two clocks closed at one instant cannot drift apart; default: this
        instant). Read between two selects, so no wait is half counted."""
        if now is None:
            now = time.monotonic()
        return now - self.t_start - self.wait_s


def install(
    loop: Optional[asyncio.AbstractEventLoop] = None,
) -> Optional[LoopBusyCounter]:
    """The counter of ``loop`` (default: the running one), installed on
    first call."""
    loop = loop or asyncio.get_running_loop()
    counter = getattr(loop, _ATTR, None)
    if counter is not None:
        return counter
    selector = getattr(loop, "_selector", None)
    if selector is None:
        return None
    counter = LoopBusyCounter()
    select = selector.select
    clock = time.monotonic

    def timed_select(timeout=None):
        if timeout is not None and timeout <= 0:
            return select(timeout)     # a poll: the loop has work queued
        t0 = clock()
        try:
            return select(timeout)
        finally:
            counter.wait_s += clock() - t0

    selector.select = timed_select
    setattr(loop, _ATTR, counter)
    return counter
