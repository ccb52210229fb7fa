"""Start the program's worker on a configuration that is data.

The worker CLI knows four Llama presets.  This launcher reads a configuration
file from ``configs/``, builds a ``ModelConfig`` from it, registers it in
``dynamo_tpu.worker.MODEL_PRESETS`` under the configuration's name and calls
``dynamo_tpu.worker.main`` — the normal path; nothing in the program changes.
Around the engine's construction it does two things the benchmark needs:
it passes the weight seed (the engine's own on-device init draws the weights),
and, once the engine stands, runs the configuration's reference check
(``reference.compare``) on the engine's own weights and writes the verdict
to ``$BENCH_CHIP_RUNDIR/reference.json``.

    python -m benchmarks.chip.worker_launch --config <file> [--rehearse] \
        <worker arguments...>
"""

from __future__ import annotations

import json
import os
import sys
import time

T_START = time.monotonic()


def model_config_from(cfg: dict, rehearse: bool):
    from dynamo_tpu.engine.config import ModelConfig

    if rehearse:
        return ModelConfig.tiny(vocab_size=cfg["rehearse"]["vocab_size"])
    return ModelConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        max_position=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])


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

    import dynamo_tpu.worker as W

    name = cfg["name"]
    mcfg = model_config_from(cfg, rehearse)
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
        from . import reference

        try:
            verdict = reference.compare(eng, seed)
        except Exception as e:  # the verdict must reach the harness
            verdict = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        stamps["reference_s"] = time.monotonic() - t1
        with open(os.path.join(rundir, "reference.json"), "w") as f:
            json.dump({"config": name, "verdict": verdict,
                       "stamps": stamps}, f)
        return eng

    W.InferenceEngine = engine_with_check
    eargs = cfg["rehearse"]["engine_args"] if rehearse else cfg["engine_args"]
    W.main(["--model", name] + list(eargs) + rest)


if __name__ == "__main__":
    main(sys.argv[1:])
