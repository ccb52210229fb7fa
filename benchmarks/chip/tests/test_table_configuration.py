"""CPU checks of a configuration whose model is a table of layer kinds
(``configs/laguna-s-2.1-ep2.json``) and of its cell's data, in seconds:

    python3 -m pytest benchmarks/chip/tests/test_table_configuration.py -q

(Two cases of ``test_references.py`` are parametrised over every
configuration of ``BENCHMARK.json`` and fail for this one by construction,
until a ``benchmark`` PR narrows them:
``test_default_map_builds_what_the_twelve_keys_built`` asserts that no
configuration has ``program_fields``, and
``test_limits_file_addresses_numbers_the_verdict_has`` holds every limit
under 0.03 and looks its statistic up in the dense reference's verdict.)
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import run as R              # noqa: E402
from benchmarks.chip import shape as S            # noqa: E402
from benchmarks.chip import worker_launch as WL   # noqa: E402

NAME = "laguna-s-2.1-ep2"
CELL = "laguna-s-ep2.codegen"
BENCH = R.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg() -> dict:
    return R.load_config(NAME)


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_file_builds_a_model_config(rehearse):
    cfg = _cfg()
    m = WL.check_configuration(cfg, rehearse)
    assert m.has_table and m.has_routed_experts
    assert len(m.layer_types) == m.num_layers == 5
    assert m.layer_types[0] == m.layer_types[4] == "full_attention"
    assert set(m.layer_types[1:4]) == {"sliding_attention"}
    assert m.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert m.experts_held == (0, m.num_routed_experts // 2)
    assert m.vocab_size == (cfg["rehearse"]["vocab_size"] if rehearse
                            else cfg["vocab_size"])
    assert WL.load_reference(cfg).__name__.endswith("laguna")


def test_the_rehearsal_model_is_the_same_keys_small():
    cfg = _cfg()
    small = cfg["rehearse"]["model"]
    for field, key in cfg["program_fields"].items():
        assert key in small, key
    assert (small["hidden_size"], small["head_dim"],
            small["num_key_value_heads"], small["sliding_window"]) == (
        64, 16, 2, 16)
    assert small["num_attention_heads_per_layer"] == [4, 6, 6, 6, 4]
    assert (small["num_routed_experts"], small["num_experts"],
            small["num_experts_per_tok"]) == (16, 8, 4)


def test_every_published_number_is_the_sources():
    """Every number of the catalog entry's ``config`` stands in the file
    under the same key, but the three in ``reduced``; the per-layer lists
    are the source's first five entries."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e.get("source_url") == _cfg()["source"])
    cfg, src = _cfg(), entry["config"]
    declared = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert sorted(declared["reduced"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, val in src.items():
        if key in cfg["reduced"]:
            assert cfg["reduced"][key]["source"] == val, key
            assert cfg["reduced"][key]["here"] == cfg[key], key
        elif isinstance(val, list) and len(val) == src["num_hidden_layers"]:
            assert cfg[key] == val[:5], key
        else:
            assert cfg[key] == val, key
    assert cfg["num_routed_experts"] == src["num_experts"] == 256


def test_the_file_says_what_it_assumed_and_where_it_runs():
    cfg = _cfg()
    assert {"router", "gating", "no_qk_norm_no_shared_gate"} <= set(
        cfg["assumed"])
    assert "two chips share each layer" in cfg["deployment"]
    assert cfg["expert_shard"] == {"index": 0, "of": 2}
    assert cfg["chips"] == 1 and cfg["engine_args"][-2:] == ["--mesh", "1,1"]
    assert "limits" in json.load(open(os.path.join(
        CHIP, "limits", f"{NAME}.json")))


def test_the_cells_worst_case_fits_the_blocks():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "codegen", 1)
    eng = R.engine_dict(_cfg()["engine_args"])
    mix = S.load_mix("codegen")
    shape = S.build_shape(mix, float(BENCH["run_seconds"]))
    bs = eng["block_size"]
    worst = max(-(-(r["total_len"] + r["max_tokens"]) // bs)
                for r in shape["requests"])
    assert mix["clients"] == eng["max_num_seqs"] == 32
    assert mix["clients"] * worst <= eng["num_blocks"] - 1
    longest = max(r["total_len"] + r["max_tokens"] for r in shape["requests"])
    assert longest <= eng["max_model_len"]
    lens = [r["total_len"] for r in shape["requests"]]
    assert min(lens) >= 2 * _cfg()["sliding_window"]     # past two windows
    assert 1800 < sum(lens) / len(lens) < 1920
    assert all(r["group"] is None for r in shape["requests"])
    assert S.reachable_decode_buckets(shape, eng) == [8, 16, 32]


def test_the_new_readers_on_hand_made_counters():
    cfg = _cfg()
    steps = [{"kind": "decode", "context_sum": 32 * 2048,
              "context_sum_window": 32 * 512,
              "kv_blocks_walked_window": 32 * 3 * 16,
              "moe_pairs": 32 * 10 * 4, "moe_pairs_held": 32 * 5 * 4,
              "moe_experts_touched": 4 * 96, "moe_load_max": 5},
             {"kind": "prefill", "context_sum": 7}]
    ctx = {"steps": steps, "config": cfg, "rehearse": False,
           "engine": {"block_size": 16}, "trace": None, "peaks": None,
           "health_end": {}}
    read = lambda name: R.load_reader(name).read(ctx)     # noqa: E731
    assert read("moe_held_pair_share") == pytest.approx(50.0)
    assert read("moe_experts_touched_share") == pytest.approx(75.0)
    assert read("moe_load_max_over_mean") == pytest.approx(5 / 1.25)
    assert read("attn_window_walk_ratio") == pytest.approx(1.5)
    for name in ("moe_step_dev_ms", "moe_expert_roofline",
                 "attn_mixed_roofline"):
        assert read(name) is None        # no trace: nothing, not an error
    # a program without the counters (the parent commit) gives nothing
    old = dict(ctx, steps=[{"kind": "decode", "context_sum": 9,
                            "kv_blocks_walked": 3}])
    for m in BENCH["per_layer"]:
        if m["workloads"] == [CELL]:
            assert R.load_reader(m["name"]).read(old) is None, m["name"]
    mixed = R.load_reader("attn_mixed_roofline")
    assert mixed.kv_bytes_attended(1, 0, cfg) == 2 * 4096     # 2 full layers
    assert mixed.kv_bytes_attended(0, 1, cfg) == 3 * 4096
    ctx2 = dict(ctx, chips=1, window=(0.0, 1.0),
                peaks={"hbm_bytes_per_s": 819e9},
                trace={"window_s": 1.0, "busy_s": 1.0,
                       "device_ops": [["paged_attention_ragged", 0.01]]})
    want = 100.0 * (2 * 32 * 2048 + 3 * 32 * 512) * 4096 / 819e9 / 0.01
    assert mixed.read(ctx2) == pytest.approx(want)
    roof = R.load_reader("moe_expert_roofline")
    assert roof.expert_bytes(1, cfg) == 3 * 3072 * 1024 * 2
