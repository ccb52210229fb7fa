"""The load generator: its own process, one thread, never imports JAX.

    python -m benchmarks.chip.loadgen <plan.json> <out.json>

The plan holds the run's shape (``shape.build_shape``), the seed for token
ids, the port, and ``t0``: the CLOCK_MONOTONIC instant at which the ramp
starts.  Open loop: request i is sent at ``t0 + due``.  Closed loop: client c
starts at ``t0 + stagger * c / clients`` and walks its plan of turns, each
sent when the previous one ended; a client that reaches the end of its plan
starts it again (a *lap*: the same lengths, fresh tokens no lap before had),
so the offered load never depends on how fast the system is.  At
``t0 + horizon`` every stream still open is cut and the records are written.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

from . import client as C
from . import shape as S


async def _one(session, plan, shape, r, due_t, records, lap=0):
    toks = S.request_tokens(shape, r, plan["seed"], plan["vocab"], lap)
    now = time.monotonic()
    if due_t > now:
        await asyncio.sleep(due_t - now)
    rec = {"idx": r["idx"], "due_t": due_t, "group": r["group"],
           "client": r.get("client"), "turn": r.get("turn"), "lap": lap,
           "send_t": time.monotonic()}
    records.append(rec)
    await C.stream_completion(session, plan["port"], plan["model"], toks,
                              r["max_tokens"], rec)
    return rec


async def _client_loop(session, plan, shape, c, mine, t0, records):
    start = t0 + shape["stagger_s"] * c / max(1, shape["clients"])
    due = start
    lap = 0
    while True:                          # until the horizon cancels it
        for r in mine:
            rec = await _one(session, plan, shape, r, due, records, lap)
            due = time.monotonic()       # closed loop: due when free
            if rec.get("errors") or rec.get("status") != 200:
                await asyncio.sleep(0.2)  # do not spin on a refusing server
        lap += 1


async def run(plan: dict) -> dict:
    shape = plan["shape"]
    t0 = plan["t0"]
    stop_t = t0 + shape["horizon_s"]
    records: list = []
    async with C.new_session() as session:
        if shape["loop"] == "open":
            tasks = [asyncio.create_task(
                _one(session, plan, shape, r, t0 + r["due"], records))
                for r in shape["requests"]]
        else:
            per: dict = {}
            for r in shape["requests"]:
                per.setdefault(r["client"], []).append(r)
            tasks = [asyncio.create_task(_client_loop(
                session, plan, shape, c, sorted(v, key=lambda r: r["turn"]),
                t0, records)) for c, v in sorted(per.items())]
        await asyncio.sleep(max(0.0, stop_t - time.monotonic()))
        for t in tasks:
            t.cancel()
        res = await asyncio.gather(*tasks, return_exceptions=True)
        crashed = [repr(x) for x in res if isinstance(x, Exception)
                   and not isinstance(x, asyncio.CancelledError)]
    for rec in records:
        if not rec.get("done") and not rec.get("errors") \
                and rec.get("status") in (200, None):
            rec["cut"] = True
    return {"records": records,
            "laps_max": max((r["lap"] for r in records), default=0),
            "crashed": crashed, "stopped_t": time.monotonic()}


def main(argv) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    out = asyncio.run(run(plan))
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
