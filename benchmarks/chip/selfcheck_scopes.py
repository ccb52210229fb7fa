#!/usr/bin/env python3
"""CPU checks of the by-scope trace reducer (``scopes.py``), no JAX, no chip:

    python3 benchmarks/chip/selfcheck_scopes.py

  * hand-made events: scope attribution by the outermost vocabulary name,
    unscoped ops, program runs cut by the trace's edge, per-run sums;
  * a small recorded v5e capture (two decode-window runs, metadata stats
    kept): ``load_events`` reads the protobuf's wire format itself, and its
    by-scope seconds must equal values computed from the same file with
    tensorflow's ``xplane_pb2`` (``testdata/decode_scopes.expected.json``);
    identity on it: the scopes' times per run of ``jit_window`` sum to that
    program's device span within 5% (the device has no gaps).
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import scopes as S            # noqa: E402
from benchmarks.chip.selfcheck import close        # noqa: E402

# model.SCOPES, spelled out: this check must run without importing JAX
# (tests/test_observability.py holds the two tuples equal)
VOCABULARY = ("embed", "qkv_proj", "rope", "kv_write", "attention", "o_proj",
              "mlp", "final_norm", "lm_head", "sample", "ctl")


def check_hand_made():
    us = 1_000_000                      # picoseconds
    ops = [
        # the first run is cut by the trace's start: its op counts, its
        # run does not
        ("%fusion.0 = f32[4] fusion(x)", 0, 50 * us,
         "jit(window)/mlp/dot_general:"),
        # a run of jit_window over [100, 600) us
        ("%fusion.1 = f32[4] fusion(x)", 100 * us, 100 * us,
         "jit(window)/qkv_proj/dot_general:"),
        ("%copy.2 = bf16[2] copy(y)", 200 * us, 50 * us,
         "jit(window)/kv_write/scatter:"),
        ("%paged.3 = bf16[8] custom-call(z)", 250 * us, 300 * us,
         "jit(window)/attention/jit(paged_attention_ragged)/pallas_call:"),
        ("%copy.4 = bf16[2] copy(p)", 550 * us, 50 * us, "cache['v'][3]:"),
        # outermost name wins; a second whole run over [700, 900) us
        ("%fusion.5 = f32[4] fusion(x)", 700 * us, 150 * us,
         "jit(window)/mlp/attention/mul:"),
        ("%slice-done.6 = bf16[2] async-done(q)", 850 * us, 50 * us, ""),
        # the last run is cut by the trace's end
        ("%fusion.7 = f32[4] fusion(x)", 1000 * us, 50 * us,
         "jit(window)/mlp/dot_general:"),
    ]
    programs = [("jit_window(1)", 0, 50 * us),
                ("jit_window(1)", 100 * us, 500 * us),
                ("jit_window(1)", 700 * us, 200 * us),
                ("jit_window(1)", 1000 * us, 50 * us)]
    r = S.reduce_events({"devices": {"/device:TPU:0": {
        "ops": ops, "programs": programs}}}, VOCABULARY)
    assert r["devices"] == 1 and close(r["busy_s"], 800e-6)
    want = {"qkv_proj": 100e-6, "kv_write": 50e-6, "attention": 300e-6,
            "mlp": 250e-6, "unscoped": 100e-6}
    assert set(r["by_scope"]) == set(want)
    for k, v in want.items():
        assert close(r["by_scope"][k], v), (k, r["by_scope"][k])
    assert close(r["coverage"], 1.0 - 100 / 800)
    assert dict(r["unscoped_ops"]) == {"copy": 50e-6, "slice-done": 50e-6}
    p = r["programs"]["jit_window"]
    assert p["runs"] == 2 and close(p["median_ms"], 0.35)
    assert close(p["mean_ms"], 0.35)
    # per run: (500 + 200) us over two runs, by scope
    assert close(p["by_scope_ms"]["attention"], 0.150)
    assert close(p["by_scope_ms"]["mlp"], 0.075)
    assert close(sum(p["by_scope_ms"].values()), 0.350)
    assert close(S.program_scope_ms(r, "window", ("qkv_proj", "mlp")), 0.125)
    assert close(S.program_scope_ms(r, "window"), 0.350)
    assert S.program_scope_ms(r, "prefill") is None
    assert S.scope_of("jit(f)/ctl/sample/x", VOCABULARY) == "ctl"
    assert S.scope_of("", VOCABULARY) == S.UNSCOPED
    empty = S.reduce_events({"devices": {}}, VOCABULARY)
    assert empty["devices"] == 0 and empty["coverage"] is None


def check_recorded():
    rec = os.path.join(HERE, "testdata", "decode_scopes.xplane.pb.gz")
    gold = os.path.join(HERE, "testdata", "decode_scopes.expected.json")
    if not os.path.exists(rec):
        return False
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.xplane.pb")
        with gzip.open(rec, "rb") as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        got = S.reduce_events(S.load_events(path), VOCABULARY)
    with open(gold) as f:
        want = json.load(f)
    assert got["devices"] == want["devices"]
    assert close(got["busy_s"], want["busy_s"], 1e-9)
    assert set(got["by_scope"]) == set(want["by_scope"])
    for k, v in want["by_scope"].items():
        assert close(got["by_scope"][k], v, 1e-9), (k, got["by_scope"][k], v)
    for name, p in want["programs"].items():
        g = got["programs"][name]
        assert g["runs"] == p["runs"], name
        for k, v in p["by_scope_ms"].items():
            assert close(g["by_scope_ms"][k], v, 1e-9), (name, k)
    # the device has no gaps: a decode program's scopes add up to its span
    w = got["programs"]["jit_window"]
    total = sum(w["by_scope_ms"].values())
    assert abs(total - w["mean_ms"]) <= 0.05 * w["mean_ms"], (
        total, w["mean_ms"])
    # every stage of the vocabulary is there to be read
    assert set(VOCABULARY) <= set(w["by_scope_ms"]), sorted(w["by_scope_ms"])
    return True


def main() -> int:
    check_hand_made()
    had = check_recorded()
    print(json.dumps({"selfcheck_scopes": "ok",
                      "recorded_trace_checked": had}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
