"""CPU check of the reader ``latent_prefill_dev_ms`` (PR 54) on a hand-made
by-scope summary, in a second:

    python3 -m pytest benchmarks/chip/tests/test_latent_prefill_dev_ms.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import run as R              # noqa: E402

NAME = "latent_prefill_dev_ms"
CELLS = ["longcat-omni-ep32.agent", "ling-flash-ep8.longgen"]


def _ctx(tmp_path, programs):
    trace_dir = tmp_path / "profile"
    trace_dir.mkdir()
    with open(trace_dir / "scopes_summary.json", "w") as f:
        json.dump({"programs": programs}, f)
    return {"health_end": {"last_profile": {"trace_dir": str(trace_dir)}}}


def test_it_is_declared_as_the_reader_says():
    declared = next(m for m in R.load_benchmark()["per_layer"]
                    if m["name"] == NAME)
    reader = R.load_reader(NAME)
    assert declared["workloads"] == CELLS
    assert all(declared[k] == getattr(reader, k.upper())
               for k in ("unit", "better", "source", "layer", "moves"))


def test_the_mean_over_the_captures_prefill_programs(tmp_path):
    """Two prefill programs of different T that ran 3 and 1 times: the
    runs' mean of the scope ``attention_latent`` alone; a decode window's
    (the absorbed walk, ``latent_step_dev_ms``) does not count."""
    programs = {
        "jit_prefill(1)": {"runs": 3, "by_scope_ms": {
            "attention_latent": 5.0, "mlp": 11.0, "moe_experts": 4.9}},
        "jit_prefill(2)": {"runs": 1, "by_scope_ms": {
            "attention_latent": 1.0, "mlp": 0.5}},
        "jit_window": {"runs": 40, "by_scope_ms": {"attention_latent": 6.8}},
    }
    assert R.load_reader(NAME).read(_ctx(tmp_path, programs)) == \
        pytest.approx((3 * 5.0 + 1.0) / 4)


@pytest.mark.parametrize("programs", [
    {},                                                     # no program
    {"jit_window": {"runs": 9, "by_scope_ms": {"attention_latent": 6.8}}},
    {"jit_prefill": {"runs": 5, "by_scope_ms": {"mlp": 8.4}}},  # no latent
])
def test_nothing_to_read_gives_nothing(tmp_path, programs):
    read = R.load_reader(NAME).read
    assert read(_ctx(tmp_path, programs)) is None
    assert read({"health_end": {}}) is None                 # no capture
