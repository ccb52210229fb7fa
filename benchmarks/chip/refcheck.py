"""A configuration against its reference, alone: no store, no frontend, no
traffic.

    python -m benchmarks.chip.refcheck --config <name> --seeds <a,b,...> \
        [--rehearse] [worker arguments...]

The engine is built as ``worker_launch`` builds it (the configuration's
``program_fields``, its worker arguments, the program's own
``dynamo_tpu.worker.main`` up to the engine's construction), then the
configuration's ``compare`` runs once per seed on the weights that seed
draws, and one line per seed goes to stdout.  Arguments that are not this
tool's go to the worker, after the configuration's own, so they win:
``--kv-dtype int8`` or ``--weight-dtype fp8`` make the control runs whose
smallest reading a limit has to stay under, ``--num-blocks 128`` makes room
for them.  The exit code is 0 when every seed was judged, whatever the
verdicts: this reads numbers, ``run.py`` decides ``correct``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


class _EngineBuilt(Exception):
    """Leaves ``dynamo_tpu.worker.main`` once the engine stands."""


def build_engine(cfg: dict, mcfg, rehearse: bool, worker_args: list,
                 seed: int):
    """The engine ``worker_launch`` would serve, without serving it."""
    import dynamo_tpu.worker as W

    built = []
    real_engine = W.InferenceEngine

    def engine_then_leave(model_config, engine_config, params=None):
        built.append(real_engine(model_config, engine_config, params=params,
                                 seed=seed))
        raise _EngineBuilt

    W.MODEL_PRESETS[cfg["name"]] = lambda: mcfg
    W.InferenceEngine = engine_then_leave
    eargs = cfg["rehearse"]["engine_args"] if rehearse else cfg["engine_args"]
    try:
        W.main(["--model", cfg["name"]] + list(eargs) + list(worker_args))
    except _EngineBuilt:
        pass
    finally:
        W.InferenceEngine = real_engine
    return built[0]


def redraw_weights(engine, seed: int) -> None:
    """Give ``engine`` the weights its constructor draws from ``seed``."""
    import jax

    from dynamo_tpu.engine import model as M

    engine.params = None          # free the old ones before the new are made
    engine.params = M.init_params_sharded(
        jax.random.PRNGKey(seed), engine.model_config, engine.mesh,
        engine.config.weight_dtype)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a configuration's name (configs/<name>.json) or "
                         "a path to such a file")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, the configuration's rehearsal model")
    args, worker_args = ap.parse_known_args(argv)
    path = (args.config if os.path.isfile(args.config)
            else os.path.join(HERE, "configs", f"{args.config}.json"))
    with open(path) as f:
        cfg = json.load(f)
    # run.py hands the launcher --seed modulo 2^31 - 1: the same here, so
    # that a seed reads here what it reads in a run
    given = [int(s) for s in args.seeds.split(",")]
    seeds = [s % (2 ** 31 - 1) for s in given]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   f"{int(cfg['chips'])}")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from . import worker_launch as WL

    try:
        mcfg = WL.check_configuration(cfg, args.rehearse, path)
    except WL.ConfigError as e:
        sys.stderr.write(f"refcheck: {e}\n")
        return 1
    engine = build_engine(cfg, mcfg, args.rehearse, worker_args, seeds[0])
    engine.cache = None                 # compare pages a cache of its own
    for i, seed in enumerate(seeds):
        if i:
            redraw_weights(engine, seed)
        verdict = WL.judge(cfg, engine, seed)
        print(json.dumps({"config": cfg["name"], "seed": given[i],
                          "worker_args": worker_args,
                          "rehearsal": args.rehearse, **verdict}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
