#!/usr/bin/env python3
"""Unit checks of the yardstick, on the CPU, in seconds (not part of tests/):

    python3 benchmarks/chip/selfcheck.py

- the shape builder offers identical totals, schedules and per-client plans
  whatever the ``--seed``, while the token ids differ, and a closed loop's
  second lap repeats no fresh token of the first;
- the percentile arithmetic and the window reduction on a hand-made sample;
- the trace reducer on hand-made events with known busy / idle / op shares /
  exposed collective time, and on the small recorded trace in ``testdata/``.
"""

from __future__ import annotations

import gzip
import json
import os
import random
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks.chip import metrics as M   # noqa: E402
from benchmarks.chip import shape as S     # noqa: E402
from benchmarks.chip import xplane as X    # noqa: E402

ENG = dict(block_size=16, max_batched_tokens=512, max_num_seqs=64,
           max_model_len=8192, prefill_buckets=[16, 32, 64, 128, 256, 512],
           decode_buckets=[8, 16, 32, 64])


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(abs(a), abs(b), 1e-12)


def check_shapes():
    for name in sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
                       if f.endswith(".json")):
        mix = S.load_mix(name)
        a = S.build_shape(mix, 50.0)
        b = S.build_shape(mix, 50.0)
        assert a["summary"] == b["summary"], name
        key = [(r.get("due"), r.get("client"), r.get("turn")) for r in
               a["requests"]]
        assert key == [(r.get("due"), r.get("client"), r.get("turn"))
                       for r in b["requests"]], name
        ta = S.request_tokens(a, a["requests"][0], 1, 32768)
        tb = S.request_tokens(b, b["requests"][0], 3000000019, 32768)
        assert len(ta) == len(tb) and ta != tb, name
        assert ta == S.request_tokens(a, a["requests"][0], 1, 32768)
        assert min(ta) >= 256 and max(ta) < 32768
        if a["loop"] == "closed":
            # a lap's fresh tokens are no other lap's; its document repeats
            r = a["requests"][0]
            n_doc = 0 if r["group"] is None else a["docs"][r["group"]]
            lap1 = S.request_tokens(a, r, 1, 32768, lap=1)
            assert lap1[:n_doc] == ta[:n_doc] and lap1[n_doc:] != ta[n_doc:]
            assert lap1 == S.request_tokens(a, r, 1, 32768, lap=1)
    # stratified lengths hit the stated mean: uniform 128..384 -> 256
    v = S.stratified({"dist": "uniform", "lo": 128, "hi": 384}, 288,
                     random.Random(0))
    assert abs(sum(v) / len(v) - 256) < 0.5
    g = [S.quantile({"dist": "gamma", "mean": 1.0, "cv": 1.5},
                    (i + 0.5) / 2000) for i in range(2000)]
    m = sum(g) / len(g)
    cv = (sum((x - m) ** 2 for x in g) / len(g)) ** 0.5 / m
    assert abs(m - 1.0) < 0.01 and abs(cv - 1.5) < 0.05, (m, cv)
    # batchgen's prompts are single chunks: three prefill programs
    bg = S.build_shape(S.load_mix("batchgen"), 50.0)
    assert S.reachable_prefill_programs(bg, ENG) == [(64, 4), (128, 8),
                                                     (256, 16)]
    assert S.reachable_decode_buckets(bg, ENG) == [8, 16, 32, 64]
    assert S.zipf_picks(4, 1.0, 25, random.Random(0)).count(0) == 12


def check_percentiles():
    assert M.percentile([], 50) is None
    assert M.percentile([5.0], 95) == 5.0
    assert M.percentile([1, 2, 3, 4], 50) == 2.5
    assert close(M.percentile(list(range(101)), 95), 95.0)
    assert close(M.percentile([10, 20, 30, 40, 50], 90), 46.0)
    # two requests, window [10, 20): one wholly inside, one starting before
    recs = [
        {"due_t": 10.5, "send_t": 10.6, "status": 200, "done": True,
         "errors": [], "completion_tokens": 4, "max_tokens": 4,
         "events": [[11.0, 1], [11.1, 1], [11.3, 1], [11.6, 1]],
         "end_t": 11.6},
        {"due_t": 8.0, "send_t": 8.0, "status": 200, "done": True,
         "errors": [], "completion_tokens": 3, "max_tokens": 3,
         "events": [[9.0, 1], [10.2, 1], [10.4, 1]], "end_t": 10.4},
        {"due_t": 19.0, "send_t": 19.0, "status": 503, "done": False,
         "errors": ["busy"], "completion_tokens": None, "max_tokens": 3,
         "events": []},
    ]
    c = M.reduce_client(recs, 10.0, 20.0, 1, open_loop=True)
    assert c["attempted"] == 3 and c["failed"] == 1 and c["completed"] == 2
    assert c["tokens_in_window"] == 6 and close(c["out_tok_s"], 0.6)
    assert close(c["ttft_p50_ms"], 500.0)          # 11.0 - due 10.5
    # tpot: (11.6-11.0)/3 = 200 ms and (10.4-9.0)/2 = 700 ms -> median 450
    assert close(c["tpot_p50_ms"], 450.0, 1e-6)
    # gaps ending in the window: 100, 200, 300, 1200, 200 ms
    assert c["n_gaps"] == 5 and close(c["itl_p95_ms"], 1020.0, 1e-6)
    # no token between 11.6 and the window's end at 20
    assert close(c["longest_silence_ms"], 8400.0, 1e-6)
    c2 = M.reduce_client(recs, 10.0, 20.0, 1, open_loop=False)
    assert close(c2["ttft_p50_ms"], 400.0, 1e-6)   # 11.0 - send 10.6


def check_reducer():
    us = 1000
    ev = {"devices": {"/device:TPU:0": {"lines": [], "programs": [
        ("jit_window(1)", 0, 500 * us), ("jit_window(1)", 600 * us, 300 * us),
        ("jit_prefill(2)", 1000 * us, 100 * us)],
        "ops": [
            ("%paged_attention_ragged.3 = bf16[8] custom-call(x)", 0, 400 * us),
            ("%copy.7 = bf16[2] copy(y)", 400 * us, 100 * us),
            ("%all-reduce.1 = f32[4] all-reduce(z)", 650 * us, 150 * us),
            ("%fusion.9 = f32[4] fusion(z)", 700 * us, 200 * us),
            ("%copy.8 = bf16[2] copy(y)", 1000 * us, 100 * us)]}},
        "host": [("schedule", 450 * us, 200 * us), ("outer", 0, 2000 * us)]}
    r = X.reduce_events(ev)
    # busy: [0,500] + [650,900] + [1000,1100] = 850 us of a 1100 us window
    assert close(r["busy_s"], 850e-6) and close(r["window_s"], 1100e-6)
    ops = dict(r["device_ops"])
    assert close(ops["paged_attention_ragged"], 400e-6)
    assert close(ops["copy"], 200e-6) and close(ops["all-reduce"], 150e-6)
    assert r["programs"]["jit_window"]["count"] == 2
    assert close(r["programs"]["jit_window"]["median_ms"], 0.4)
    # all-reduce [650,800] with a fusion over [700,900]: 50 us exposed
    assert close(r["collective_exposed_s"], 50e-6)
    assert r["idle_gaps"][0][0] == "schedule" and close(r["idle_gaps"][0][1],
                                                        150e-6)
    assert r["idle_gaps"][1][0] == "outer"
    rec = os.path.join(HERE, "testdata", "decode_window.xplane.pb.gz")
    gold = os.path.join(HERE, "testdata", "decode_window.expected.json")
    if os.path.exists(rec):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "t.xplane.pb")
            with gzip.open(rec, "rb") as src, open(path, "wb") as dst:
                shutil.copyfileobj(src, dst)
            got = X.reduce_events(X.load_events(path))
        with open(gold) as f:
            want = json.load(f)
        assert got["devices"] == want["devices"]
        # ProfileData hands out whole nanoseconds, the file holds picoseconds
        for k in ("busy_s", "window_s"):
            assert close(got[k], want[k], 1e-4), (k, got[k], want[k])
        g_ops, w_ops = dict(got["device_ops"]), want["device_ops"]
        for k, v in w_ops.items():
            assert close(g_ops[k], v, 1e-4), (k, g_ops[k], v)
        for k, v in want["programs"].items():
            assert got["programs"][k]["count"] == v["count"], k
            assert close(got["programs"][k]["median_ms"], v["median_ms"],
                         1e-4), k
        return True
    return False


def main() -> int:
    check_shapes()
    check_percentiles()
    had_trace = check_reducer()
    print(json.dumps({"selfcheck": "ok", "recorded_trace_checked": had_trace}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
