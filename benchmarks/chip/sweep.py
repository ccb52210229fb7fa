"""Find an open-loop cell's knee: several rates in one server's life.

    python3 -m benchmarks.chip.sweep --workload m7b-l16.chat \
        --rates 2.5,3,3.5,4 --seconds 40

A tool beside the benchmark, not a run: it prints one table row per rate and
no result line.  The knee is the highest rate at which completions keep up
with arrivals — the number of requests in flight is no higher in the last
third of the window than in the first.  The cell's rate is 0.8 of it, moved
down until one decode bucket holds four fifths of the decode steps (the
``bucket_share`` column), and is written into the mix's file by hand.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

from . import deploy as D
from . import metrics as M
from . import run as R


def in_flight(records: list, t: float) -> int:
    n = 0
    for r in records:
        ev = r.get("events") or []
        end = ev[-1][0] if (ev and not r.get("cut")) else float("inf")
        if r["send_t"] <= t < end:
            n += 1
    return n


def mean_in_flight(records: list, a: float, b: float) -> float:
    ts = [a + (b - a) * (i + 0.5) / 40 for i in range(40)]
    return sum(in_flight(records, t) for t in ts) / len(ts)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--cache-dir", default=os.path.join(R.HERE, ".cache"))
    args = ap.parse_args()
    args.trace = 1     # StepStats on; no profile is taken
    rates = [float(x) for x in args.rates.split(",")]
    _, cell, cfg, mix, eng, vocab = R.cell_parts(args)
    if mix["loop"] != "open":
        sys.stderr.write("a closed loop has no rate to sweep\n")
        return 1
    rundir = os.path.join(R.RUN_DIR, f"{cell['name']}-sweep")
    setup: dict = {}
    dep, _, device, _ = R.launch(cell, cfg, args, rundir, setup)
    rows = []
    try:
        steps: list = []
        asyncio.run(R.warm_up(dep, R.shape_for(mix, args, rate=max(rates)),
                              eng, args.seed, vocab, steps))
        for rate in rates:
            shape = R.shape_for(mix, args, rate=rate)
            got = R.drive(dep, shape, args.seed, vocab, rundir, 0,
                          tag=f"_{rate}")
            w0, w1 = got["w0"], got["w1"]
            recs = got["lg"]["records"]
            c = M.reduce_client(recs, w0, w1, int(cell["chips"]), True)
            third = (w1 - w0) / 3
            first = mean_in_flight(recs, w0, w0 + third)
            last = mean_in_flight(recs, w1 - third, w1)
            dec = [r for r in R.read_jsonl(os.path.join(rundir,
                                                        "stepstats.jsonl"))
                   if r.get("kind") == "decode"
                   and w0 <= r.get("t_dispatch", 0) < w1]
            share: dict = {}
            for r in dec:
                share[r["bucket"]] = share.get(r["bucket"], 0) + 1
            row = {
                "rate": rate,
                "offered_out_tok_s":
                    shape["summary"]["offered_in_window"]["output_tok_s"],
                "served_out_tok_s": c["tokens_in_window"] / (w1 - w0),
                "in_flight_first_third": round(first, 2),
                "in_flight_last_third": round(last, 2),
                "keeps_up": last <= first + 1.0,
                "ttft_p50_ms": c["ttft_p50_ms"], "ttft_p95_ms": c["ttft_p95_ms"],
                "tpot_p50_ms": c["tpot_p50_ms"], "itl_p95_ms": c["itl_p95_ms"],
                "failed": c["failed"], "attempted": c["attempted"],
                "bucket_share": {str(k): round(v / max(1, len(dec)), 3)
                                 for k, v in sorted(share.items())},
                "window_compiles": got["h1"]["compile"]["compiles_total"]
                - got["h0"]["compile"]["compiles_total"],
            }
            rows.append(row)
            print(json.dumps({"sweep_row": row}), flush=True)
            time.sleep(8.0)         # let the cut streams drain
    except D.DeployFailed as e:
        sys.stderr.write(f"sweep failed: {e}\n")
        return 1
    finally:
        dep.stop_all()
    ok = [r["rate"] for r in rows if r["keeps_up"] and not r["failed"]]
    print(json.dumps({"sweep": {"cell": cell["name"], "device": device,
                                "knee": max(ok) if ok else None,
                                "rows": len(rows)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
