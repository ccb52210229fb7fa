"""Start the program's worker on a configuration that is data.

The worker CLI knows four Llama presets.  This launcher reads a configuration
file from ``configs/``, builds a ``ModelConfig`` from it, registers it in
``dynamo_tpu.worker.MODEL_PRESETS`` under the configuration's name and calls
``dynamo_tpu.worker.main`` — the normal path; nothing in the program changes.
Around the engine's construction it does two things the benchmark needs:
it passes the weight seed (the engine's own on-device init draws the weights),
and, once the engine stands, runs the configuration's reference check on the
engine's own weights and writes the verdict to
``$BENCH_CHIP_RUNDIR/reference.json``.

The configuration's file says how both are done:

- ``program_fields``: ``{"<ModelConfig field>": "<key of this file>"}``.  A
  value is passed as the file gives it, nested objects included.  Absent,
  ``DEFAULT_PROGRAM_FIELDS`` (the twelve Llama-class pairs) is the map.  With
  ``--rehearse`` the same map reads ``rehearse.model`` (the same keys at small
  sizes); a file without one rehearses ``ModelConfig.tiny``.
- ``reference``: the name of a module ``references/<name>.py`` whose
  ``compare(engine, seed)`` judges it (the contract is the docstring of
  ``references/__init__.py``).  Absent, ``reference.py`` judges.
- ``limits/<configuration's name>.json``, where there is one, holds the
  limits read on the chip for this configuration.  They are applied on top of
  the module's own verdict, so a configuration's limit can only tighten it.

    python -m benchmarks.chip.worker_launch --config <file> [--rehearse] \
        <worker arguments...>
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))

# the Llama-class keys of a published ``config.json``, by the program's field
DEFAULT_PROGRAM_FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_layers": "num_hidden_layers",
    "num_heads": "num_attention_heads",
    "num_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "max_position": "max_position_embeddings",
    "tie_word_embeddings": "tie_word_embeddings",
    "dtype": "torch_dtype",
}


class ConfigError(Exception):
    """The configuration's file asks for what the program or the benchmark
    does not have; raised before anything touches the chip."""


def _where(cfg: dict, path: str) -> str:
    return path or f"configuration {cfg.get('name')!r}"


def model_config_from(cfg: dict, rehearse: bool, path: str = ""):
    """The program's ``ModelConfig`` as the configuration's file maps it."""
    from dynamo_tpu.engine.config import ModelConfig

    where = _where(cfg, path)
    src = cfg
    if rehearse:
        src = cfg["rehearse"].get("model")
        if src is None:
            return ModelConfig.tiny(vocab_size=cfg["rehearse"]["vocab_size"])
        where += " (rehearse.model)"
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {}
    for field, key in cfg.get("program_fields",
                              DEFAULT_PROGRAM_FIELDS).items():
        if field not in known:
            raise ConfigError(
                f"{where}: program_fields maps key {key!r} to field "
                f"{field!r}, which ModelConfig does not have "
                f"(it has {sorted(known)})")
        if key not in src:
            raise ConfigError(
                f"{where}: program_fields reads field {field!r} from key "
                f"{key!r}, which is not there")
        kw[field] = src[key]
    mcfg = ModelConfig(**kw)
    if rehearse and mcfg.vocab_size != cfg["rehearse"]["vocab_size"]:
        raise ConfigError(
            f"{where}: vocabulary {mcfg.vocab_size} is not rehearse."
            f"vocab_size {cfg['rehearse']['vocab_size']}, which the "
            f"harness draws its tokens from")
    return mcfg


def reference_file(cfg: dict, path: str = ""):
    """The file of the module that judges ``cfg``; None: ``reference.py``."""
    name = cfg.get("reference")
    if name is None:
        return None
    file = os.path.join(HERE, "references", f"{name}.py")
    if not os.path.isfile(file):
        raise ConfigError(
            f"{_where(cfg, path)}: reference {name!r} is not a module of "
            f"benchmarks/chip/references (no {file})")
    return file


def load_reference(cfg: dict):
    """The module whose ``compare`` judges ``cfg``, found by path as
    ``run.load_reader`` finds a reader."""
    file = reference_file(cfg)
    if file is None:
        from . import reference

        return reference
    name = cfg["reference"].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.references." + name, file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_limits(name: str) -> dict:
    """``limits/<configuration>.json``'s ``limits``: statistic of the
    verdict (a dotted path) -> the most it may read."""
    file = os.path.join(HERE, "limits", f"{name}.json")
    if not os.path.isfile(file):
        return {}
    with open(file) as f:
        return json.load(f)["limits"]


def check_configuration(cfg: dict, rehearse: bool, path: str = ""):
    """What the file can get wrong, found without the chip; returns the
    ``ModelConfig`` it builds."""
    mcfg = model_config_from(cfg, rehearse, path)
    reference_file(cfg, path)
    load_limits(cfg["name"])
    return mcfg


def stat_of(verdict: dict, dotted: str) -> float:
    """The number a limit addresses, as ``both.rms_rel``."""
    v = verdict
    for part in dotted.split("."):
        v = v[part]
    return float(v)


def judge(cfg: dict, engine, seed: int) -> dict:
    """The verdict of the configuration's reference on ``engine``: the
    module's own, then the configuration's limits on top.  A reference that
    cannot read a quantised engine's ``{"q", "s"}`` leaves is given the
    weights the same seed draws before quantisation."""
    try:
        mod = load_reference(cfg)
        kw = {}
        if engine.config.weight_dtype != "bf16":
            import jax

            from dynamo_tpu.engine import model as M

            kw["ref_params"] = M.init_params_sharded(
                jax.random.PRNGKey(seed), engine.model_config, engine.mesh,
                "bf16")
        verdict = mod.compare(engine, seed, **kw)
        compared = []
        for stat, limit in load_limits(cfg["name"]).items():
            value = stat_of(verdict, stat)
            compared.append({"stat": stat, "value": value, "limit": limit})
            if not value <= limit:
                verdict["ok"] = False
        verdict["limits"] = compared
    except Exception as e:      # the verdict must reach the harness
        verdict = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    verdict["reference"] = cfg.get("reference") or "reference"
    return verdict


def main(argv) -> None:
    i = argv.index("--config")
    path = argv[i + 1]
    rest = argv[:i] + argv[i + 2:]
    rehearse = "--rehearse" in rest
    rest = [a for a in rest if a != "--rehearse"]
    with open(path) as f:
        cfg = json.load(f)
    rundir = os.environ["BENCH_CHIP_RUNDIR"]
    seed = int(os.environ.get("BENCH_CHIP_WEIGHT_SEED", "0"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        mcfg = check_configuration(cfg, rehearse, path)
    except ConfigError as e:
        sys.exit(f"worker_launch: {e}")

    import dynamo_tpu.worker as W

    name = cfg["name"]
    W.MODEL_PRESETS[name] = lambda: mcfg
    real_engine = W.InferenceEngine
    stamps = {"imports_s": time.monotonic() - T_START}

    def engine_with_check(model_config, engine_config, params=None):
        t0 = time.monotonic()
        eng = real_engine(model_config, engine_config, params=params,
                          seed=seed)
        import jax

        jax.block_until_ready(eng.params)
        stamps["engine_build_s"] = time.monotonic() - t0
        stamps["autotune"] = {
            k: eng.attention_impl_choice.get(k)
            for k in ("autotune_cache_hit", "tiles")}
        t1 = time.monotonic()
        verdict = judge(cfg, eng, seed)
        stamps["reference_s"] = time.monotonic() - t1
        with open(os.path.join(rundir, "reference.json"), "w") as f:
            json.dump({"config": name, "reference": verdict["reference"],
                       "verdict": verdict, "stamps": stamps}, f)
        return eng

    W.InferenceEngine = engine_with_check
    eargs = cfg["rehearse"]["engine_args"] if rehearse else cfg["engine_args"]
    W.main(["--model", name] + list(eargs) + rest)


if __name__ == "__main__":
    main(sys.argv[1:])
