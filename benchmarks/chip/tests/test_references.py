"""CPU checks of how a configuration's file reaches the program and its
reference (toy sizes, float32, a minute in all):

    python3 -m pytest benchmarks/chip/tests -q

``fixtures/tiny-moe.json`` is test data, not a benchmark configuration: the
program's other model class with its own ``program_fields`` and
``reference``, there to show that a class gets through the launcher and the
check as files, with no edit to either.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import types

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import deploy as D               # noqa: E402
from benchmarks.chip import refcheck as RC            # noqa: E402
from benchmarks.chip import run as R                  # noqa: E402
from benchmarks.chip import worker_launch as WL       # noqa: E402

BENCH = R.load_benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
MOE_FILE = os.path.join(HERE, "fixtures", "tiny-moe.json")
SEED = 2500000407


def moe_fixture() -> dict:
    with open(MOE_FILE) as f:
        return json.load(f)


# ------------------------- model fields as data -----------------------------


@pytest.mark.parametrize("config", CONFIGS)
def test_default_map_builds_what_the_twelve_keys_built(config):
    """A file without ``program_fields`` gives the ``ModelConfig`` that the
    launcher's hard-wired mapping gave before PR 28."""
    from dynamo_tpu.engine.config import ModelConfig

    cfg = R.load_config(config)
    assert "program_fields" not in cfg and "reference" not in cfg
    before = ModelConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        max_position=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])
    assert WL.model_config_from(cfg, False) == before
    assert WL.model_config_from(cfg, True) == ModelConfig.tiny(
        vocab_size=cfg["rehearse"]["vocab_size"])
    WL.check_configuration(cfg, False)
    WL.check_configuration(cfg, True)
    assert WL.load_reference(cfg).__name__ == "benchmarks.chip.reference"


def test_program_fields_reach_fields_the_default_map_lacks():
    from dynamo_tpu.engine.config import ModelConfig

    cfg = moe_fixture()
    assert WL.model_config_from(cfg, False) == dataclasses.replace(
        ModelConfig.tiny_moe(512), moe_capacity_factor=4.0)
    small = WL.model_config_from(cfg, True)       # rehearse.model, same map
    assert (small.num_experts, small.hidden_size, small.vocab_size) == (
        4, 32, cfg["rehearse"]["vocab_size"])
    assert small.tie_word_embeddings


def _broken(kind: str) -> dict:
    cfg = moe_fixture()
    if kind == "field":
        cfg["program_fields"]["kv_latent_rank"] = "kv_lora_rank"
        cfg["kv_lora_rank"] = 512
    elif kind == "key":
        del cfg["num_local_experts"]
    elif kind == "reference":
        cfg["reference"] = "latent_attention"
    elif kind == "rehearsal_vocab":
        cfg["rehearse"]["model"]["vocab_size"] = 300
    return cfg


@pytest.mark.parametrize("kind,rehearse,names", [
    ("field", False, ["kv_latent_rank", "kv_lora_rank", "ModelConfig"]),
    ("key", False, ["num_experts", "num_local_experts"]),
    ("reference", False, ["latent_attention", "references"]),
    ("rehearsal_vocab", True, ["300", "rehearse.model"]),
])
def test_what_the_file_gets_wrong_is_named(kind, rehearse, names):
    with pytest.raises(WL.ConfigError) as e:
        WL.check_configuration(_broken(kind), rehearse, "some/file.json")
    for n in names + ["some/file.json"]:
        assert n in str(e.value), (n, str(e.value))


@pytest.mark.parametrize("kind", ["field", "reference"])
def test_a_run_on_such_a_file_fails_before_anything_starts(kind, tmp_path,
                                                           monkeypatch):
    """``run.launch`` raises what ``run_cell`` turns into exit 1, and the
    launcher itself exits with the message: no wait for READY_TIMEOUT_S."""
    cfg = _broken(kind)
    started = []
    monkeypatch.setattr(D, "Deployment",
                        lambda *a, **k: started.append(a))
    args = types.SimpleNamespace(rehearse=False, seed=1, trace=0,
                                 cache_dir=str(tmp_path / "cache"))
    cell = {"name": "x.y", "config": cfg["name"], "chips": 1}
    with pytest.raises(D.DeployFailed, match=cfg["name"]):
        R.launch(cell, cfg, args, str(tmp_path / "run"), {})
    assert not started
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(cfg))
    monkeypatch.setenv("BENCH_CHIP_RUNDIR", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        WL.main(["--config", str(path)])
    assert str(path) in str(e.value.code)


# ------------------------- the reference as a file --------------------------


def _engine(mcfg, seed=SEED):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import InferenceEngine

    return InferenceEngine(mcfg, EngineConfig(
        num_blocks=128, max_num_seqs=8, max_num_batched_tokens=128,
        max_model_len=512, attention_impl="einsum"), seed=seed)


@pytest.fixture(scope="module")
def moe_engine():
    return _engine(WL.model_config_from(moe_fixture(), False))


@pytest.fixture(scope="module")
def dense_engine():
    from dynamo_tpu.engine.config import ModelConfig

    return _engine(ModelConfig.tiny(512))


def _with(engine, **changed):
    """``engine`` as ``compare`` sees it, with some attributes replaced."""
    seen = {k: getattr(engine, k)
            for k in ("model_config", "config", "mesh", "params")}
    seen.update(changed)
    return types.SimpleNamespace(**seen)


def _zeroed(params, edits):
    """``params`` with ``layers[name][index]`` zeroed for each edit."""
    out = dict(params, layers=dict(params["layers"]))
    for name, index in edits:
        out["layers"][name] = out["layers"][name].at[index].set(0.0)
    return out


def test_another_class_passes_its_own_reference_and_not_the_default(
        moe_engine):
    cfg = moe_fixture()
    own = WL.judge(cfg, moe_engine, SEED)
    assert own["ok"] and own["reference"] == "moe_topk", own
    assert max(own["prefill"]["rel"], own["decode"]["rel"]) < 1e-4
    # the same engine before the Llama-class reference: the dispatch, not a
    # lenient check, is what made it pass
    cfg.pop("reference")
    other = WL.judge(cfg, moe_engine, SEED)
    assert not other["ok"] and other["reference"] == "reference"
    assert "error" in other


@pytest.mark.parametrize("control", ["an_experts_weights_lost",
                                     "tokens_dropped_at_capacity"])
def test_moe_reference_refuses_a_broken_served_path(moe_engine, control):
    mod = WL.load_reference(moe_fixture())
    if control == "an_experts_weights_lost":
        broken = _with(moe_engine, params=_zeroed(
            moe_engine.params, [("w_down", (slice(None), 3))]))
    else:       # the program's capacity dispatch at a factor that drops
        broken = _with(moe_engine, model_config=dataclasses.replace(
            moe_engine.model_config, moe_capacity_factor=0.5))
    v = mod.compare(broken, SEED, ref_params=moe_engine.params)
    assert not v["ok"], v
    assert max(v["prefill"]["rel"], v["decode"]["rel"]) > 0.03


@pytest.mark.parametrize("control", ["sound", "a_layer_dropped",
                                     "rope_base_halved"])
def test_default_reference_refuses_a_broken_served_path(dense_engine,
                                                        control,
                                                        monkeypatch):
    """``reference.py``'s own broken-path control at ``tiny``."""
    from dynamo_tpu.engine import model as M

    from benchmarks.chip import reference

    seen = dense_engine
    if control == "a_layer_dropped":    # layer 1 adds nothing to the stream
        seen = _with(dense_engine, params=_zeroed(
            dense_engine.params, [("wo", 1), ("w_down", 1)]))
    elif control == "rope_base_halved":
        rope = M._rope
        monkeypatch.setattr(M, "_rope",
                            lambda x, pos, theta: rope(x, pos, theta / 2))
    v = reference.compare(seen, SEED, ref_params=dense_engine.params)
    worst = max(v["prefill"]["rel"], v["decode"]["rel"])
    if control == "sound":
        assert v["ok"] and worst < 1e-4, v
    else:
        assert not v["ok"] and worst > reference.REL_TOL, v


def test_a_configurations_limits_only_tighten(dense_engine, monkeypatch):
    cfg = {"name": "no-such-configuration"}
    plain = WL.judge(cfg, dense_engine, SEED)
    assert plain["ok"] and plain["limits"] == []
    monkeypatch.setattr(WL, "load_limits",
                        lambda name: {"both.rms_rel": 1e-12})
    tight = WL.judge(cfg, dense_engine, SEED)
    assert not tight["ok"]
    (c,) = tight["limits"]
    assert c["stat"] == "both.rms_rel" and c["value"] > c["limit"] == 1e-12
    # a loose limit does not turn a refused verdict into a pass
    monkeypatch.setattr(WL, "load_limits", lambda name: {"decode.rel": 1.0})
    broken = _with(dense_engine, params=_zeroed(
        dense_engine.params, [("wo", 1), ("w_down", 1)]))
    mod = WL.load_reference(cfg)
    monkeypatch.setattr(
        WL, "load_reference", lambda cfg: types.SimpleNamespace(
            compare=lambda e, s: mod.compare(
                e, s, ref_params=dense_engine.params)))
    assert not WL.judge(cfg, broken, SEED)["ok"]


@pytest.mark.parametrize("config", CONFIGS)
def test_limits_file_addresses_numbers_the_verdict_has(config, dense_engine):
    limits = WL.load_limits(config)
    verdict = WL.judge({"name": "no-such-configuration"}, dense_engine, SEED)
    for stat, limit in limits.items():
        assert WL.stat_of(verdict, stat) >= 0 and 0 < limit < 0.03, stat


# ------------------------- the check that runs alone ------------------------


def test_redrawn_weights_are_the_constructors(dense_engine):
    import jax
    import numpy as np

    eng = _with(dense_engine)
    first = jax.tree.leaves(eng.params)
    RC.redraw_weights(eng, SEED + 1)
    other = jax.tree.leaves(eng.params)
    assert any(not np.array_equal(a, b) for a, b in zip(first, other))
    RC.redraw_weights(eng, SEED)
    again = jax.tree.leaves(eng.params)
    assert len(first) == len(again)
    for a, b in zip(first, again):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("config,extra,reference", [
    ("mistral-7b-v0.3-l16", [], "reference"),
    ("mistral-7b-v0.3-tp4", [], "reference"),
    ("mistral-7b-v0.3-l16", ["--weight-dtype", "int8"], "reference"),
    (MOE_FILE, [], "moe_topk"),
])
def test_refcheck_rehearsal_prints_a_verdict_per_seed(config, extra,
                                                      reference):
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.refcheck", "--config",
         config, "--seeds", f"{SEED},3000000019", "--rehearse"] + extra,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln["seed"] for ln in lines] == [SEED, 3000000019]
    for ln in lines:
        assert ln["rehearsal"] and ln["reference"] == reference
        assert ln["worker_args"] == extra
        assert "error" not in ln, ln
        # float32 toys: sound reads 1e-6, an int8 weight path a percent
        assert (ln["decode"]["rms_rel"] > 1e-3) == bool(extra)
    assert lines[0]["prefill"] != lines[1]["prefill"]
