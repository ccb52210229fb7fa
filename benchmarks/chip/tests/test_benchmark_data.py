"""CPU checks of the benchmark's data, in seconds, no device work:

    python3 -m pytest benchmarks/chip/tests -q

Every cell, mix, configuration and per-layer metric of ``BENCHMARK.json`` is
a case of its own.  The file lives under ``benchmarks/chip`` because a
``benchmark`` PR may add files nowhere else; a later PR may move it under
``tests/`` so that the tier-1 run counts it.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import loadgen as L          # noqa: E402
from benchmarks.chip import run as R              # noqa: E402
from benchmarks.chip import shape as S            # noqa: E402

BENCH = R.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
CLOSED = [m for m in MIXES if S.load_mix(m)["loop"] == "closed"]
VOCAB = 32768


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_names_a_configuration_and_a_mix(cell):
    w = CELLS[cell]
    declared = {c["name"]: c for c in BENCH["configs"]}
    assert w["config"] in declared
    assert os.path.isfile(os.path.join(ROOT, declared[w["config"]]["file"]))
    cfg = R.load_config(w["config"])
    assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"]
    assert os.path.isfile(os.path.join(CHIP, "traffic",
                                       w["traffic"] + ".json"))
    names = [m["name"] for m in R.metrics_of(BENCH, cell, "end_to_end")]
    assert "setup_s" in names and len(names) >= 2
    assert R.metrics_of(BENCH, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader_and_cells(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    reader = R.load_reader(metric)
    for k in ("unit", "better", "source", "layer", "moves"):
        assert m[k] == getattr(reader, k.upper()), k
    assert m["workloads"], "a metric lists the cells it can be read in"
    for cell in m["workloads"]:
        assert cell in CELLS
        assert m["moves"] in [e["name"] for e in
                              R.metrics_of(BENCH, cell, "end_to_end")]


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("mix", MIXES)
def test_shape_is_the_same_for_every_seed(mix):
    a = S.build_shape(S.load_mix(mix), float(BENCH["run_seconds"]))
    b = S.build_shape(S.load_mix(mix), float(BENCH["run_seconds"]))
    assert a["summary"] == b["summary"]
    assert a["requests"] == b["requests"]
    r = a["requests"][0]
    t1 = S.request_tokens(a, r, 1, VOCAB)
    t2 = S.request_tokens(a, r, 3000000019, VOCAB)
    assert len(t1) == len(t2) == r["total_len"] and t1 != t2


def _lapped_run(mix_name, monkeypatch, turns=2, horizon=0.4):
    """``loadgen.run`` on a 2-turn plan against a server that answers at
    once: every client outruns its plan many times over."""
    mix = dict(S.load_mix(mix_name), requests_per_client=turns)
    shape = S.build_shape(mix, 0.1, length_scale=8, ramp_s=0.1)
    shape["horizon_s"] = horizon
    shape["stagger_s"] = 0.0
    sent = []

    async def answer(session, port, model, toks, max_tokens, rec):
        rec.update(status=200, done=True, errors=[], events=[],
                   completion_tokens=max_tokens, max_tokens=max_tokens,
                   prompt_tokens_sent=len(toks))
        sent.append((rec["idx"], rec["lap"], rec["group"], list(toks)))
        await asyncio.sleep(0.002)
        return rec

    monkeypatch.setattr(L.C, "stream_completion", answer)
    plan = {"shape": shape, "seed": 2500000301, "vocab": 512, "port": 1,
            "model": "m", "t0": time.monotonic()}
    out = asyncio.run(L.run(plan))
    return shape, out, sent


@pytest.mark.parametrize("mix", CLOSED)
def test_a_client_that_outruns_its_plan_laps_on_fresh_tokens(mix,
                                                             monkeypatch):
    shape, out, sent = _lapped_run(mix, monkeypatch)
    assert not out["crashed"] and out["laps_max"] >= 2
    assert out["laps_max"] == max(lap for _, lap, _, _ in sent)
    fresh = {}
    for idx, lap, group, toks in sent:
        n_doc = 0 if group is None else shape["docs"][group]
        doc, tail = toks[:n_doc], toks[n_doc:]
        assert len(tail) == shape["requests"][idx]["prompt_len"]
        if group is not None:       # a document repeats, as within a lap
            assert doc == S.doc_tokens(shape, group, 2500000301, 512)
        fresh.setdefault(idx, {})[lap] = tuple(tail)
    for idx, laps in fresh.items():
        long_enough = len(next(iter(laps.values()))) >= 4
        if long_enough:             # no lap repeats another's fresh tokens
            assert len(set(laps.values())) == len(laps), idx
    # and no lapped prompt equals ANY first-lap prompt (nothing to hit)
    first = {t for laps in fresh.values() for lap, t in laps.items()
             if lap == 0 and len(t) >= 4}
    later = {t for laps in fresh.values() for lap, t in laps.items()
             if lap > 0 and len(t) >= 4}
    assert first and later and not (first & later)


def test_tp4_configuration_is_the_whole_model_at_l16_widths():
    from benchmarks.chip.worker_launch import model_config_from

    l16 = R.load_config("mistral-7b-v0.3-l16")
    tp4 = R.load_config("mistral-7b-v0.3-tp4")
    a, b = model_config_from(l16, False), model_config_from(tp4, False)
    assert (a.num_layers, b.num_layers) == (16, 32)
    for k in ("vocab_size", "hidden_size", "intermediate_size", "num_heads",
              "num_kv_heads", "head_dim", "rope_theta", "rms_norm_eps",
              "max_position", "tie_word_embeddings", "dtype"):
        assert getattr(a, k) == getattr(b, k), k
    assert tp4["reduced"] == {} and tp4["source"] == l16["source"]
    assert tp4["engine_args"][-2:] == ["--mesh", "1,4"]
    assert tp4["engine_args"][:-1] == l16["engine_args"][:-1]


def test_collective_exposed_share_on_a_hand_made_trace():
    read = R.load_reader("collective_exposed_share").read
    trace = {"busy_s": 2.0, "window_s": 3.0, "collective_exposed_s": 0.25,
             "device_ops": [["fusion", 1.2], ["all-reduce", 0.6],
                            ["copy", 0.2]]}
    assert read({"trace": trace}) == pytest.approx(12.5)
    one_chip = dict(trace, collective_exposed_s=0.0,
                    device_ops=[["fusion", 1.8], ["copy", 0.2]])
    assert read({"trace": one_chip}) is None      # nothing to read: not 0
    assert read({"trace": None}) is None


def test_window_compiles_docqa_reads_its_sibling():
    read = R.load_reader("window_compiles.docqa").read
    assert read({"window_compiles": 2}) == 2.0


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_roofline_bytes_are_one_chips_share(config):
    """``attn_decode_roofline`` divides the KV bytes by the chips: at 4
    chips a chip reads 2 of the 8 KV heads."""
    cfg = R.load_config(config)
    reader = R.load_reader("attn_decode_roofline")
    per_tok = reader.kv_bytes_attended(1, cfg)
    assert per_tok == (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
                       * cfg["num_hidden_layers"])
    ctx = {"trace": {"window_s": 1.0, "busy_s": 1.0,
                     "device_ops": [["paged_attention_ragged", 0.5]]},
           "peaks": {"hbm_bytes_per_s": 819e9}, "window": (0.0, 1.0),
           "steps": [{"kind": "decode", "context_sum": 10 ** 6}],
           "config": cfg, "chips": cfg["chips"]}
    want = 100.0 * per_tok * 1e6 / cfg["chips"] / 819e9 / 0.5
    assert reader.read(ctx) == pytest.approx(want)
    assert reader.read(ctx) < 100.0


def test_benchmark_json_keeps_to_its_size_limits():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for w in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(w["why"]) <= 200, w["name"]
