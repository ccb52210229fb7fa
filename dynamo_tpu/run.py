"""Single-command launcher: ``in=X out=Y`` like the reference's dynamo-run
(ref: launch/dynamo-run/src/main.rs:31 — ``dynamo-run in=[http|text|batch:…]
out=[auto|mocker|echo|dyn://…]``).

    python -m dynamo_tpu.run in=text out=engine --model tiny
    python -m dynamo_tpu.run in=http out=mocker --port 8000
    python -m dynamo_tpu.run in=batch:prompts.jsonl out=engine --model 1b \
        --weights /models/llama3-1b

Inputs: ``http`` (OpenAI frontend, in-process engine — no cluster needed),
``text`` (interactive REPL), ``batch:FILE`` (JSONL prompts → JSONL results).
Outputs: ``engine`` (JAX engine), ``mocker`` (device-free simulator),
``echo`` (token echo — protocol debugging).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from typing import Optional

from .engine.config import EngineConfig, ModelConfig
from .llm.protocols import BackendOutput
from .runtime.context import Context
from .utils.logging import get_logger

log = get_logger("run")

MODEL_PRESETS = {
    "tiny": ModelConfig.tiny,
    "1b": ModelConfig.llama3_1b,
    "8b": ModelConfig.llama3_8b,
    "70b": ModelConfig.llama3_70b,
    "mixtral": ModelConfig.mixtral_8x7b,
}


class EchoEngine:
    """out=echo: stream the prompt's tokens back (ref: Output::Echo)."""

    async def generate(self, request, context):
        delay = 0.01
        toks = list(request.get("token_ids", []))
        for i, t in enumerate(toks):
            await asyncio.sleep(delay)
            yield {"token_ids": [t], "index": i,
                   "finished": i == len(toks) - 1,
                   "finish_reason": "stop" if i == len(toks) - 1 else None,
                   "num_prompt_tokens": len(toks)}

    async def start(self):
        pass

    async def stop(self):
        pass


def build_output(args):
    """Engine for the ``out=`` side."""
    if args.out == "echo":
        return EchoEngine()
    if args.out == "mocker":
        from .mocker.engine import MockEngine

        return MockEngine(EngineConfig(
            num_blocks=args.num_blocks, block_size=args.block_size,
        ))
    # out=engine
    from .engine.engine import InferenceEngine

    model_cfg = MODEL_PRESETS[args.model]()
    dp, tp = (int(x) for x in args.mesh.split(","))
    params = None
    if args.weights:
        from .engine.weights import (
            load_hf_params, load_hf_params_sharded, model_config_from_hf,
        )
        import os

        if os.path.exists(os.path.join(args.weights, "config.json")):
            model_cfg = model_config_from_hf(args.weights)
        if dp * tp > 1:
            # stream each checkpoint shard straight onto device shards —
            # peak host memory stays at one tensor, not the whole model
            from .engine import model as model_lib

            mesh = model_lib.make_mesh((dp, tp))
            params = load_hf_params_sharded(args.weights, model_cfg, mesh)
        else:
            params = load_hf_params(args.weights, model_cfg)
    eng_cfg = EngineConfig(
        num_blocks=args.num_blocks, block_size=args.block_size,
        max_model_len=min(args.max_model_len, model_cfg.max_position),
        mesh_shape=(dp, tp),
    )
    return InferenceEngine(model_cfg, eng_cfg, params=params)


def build_tokenizer(args) -> Optional[object]:
    from .serving import load_tokenizer

    path = args.tokenizer or args.weights
    if path is None:
        return None
    try:
        return load_tokenizer(path)
    except Exception:
        log.warning("no tokenizer at %s — running token-id mode", path)
        return None


async def run_text(engine, tokenizer, args) -> None:
    """Interactive REPL (ref: Input::Text)."""
    await engine.start()
    print("dynamo-tpu text mode — empty line exits", file=sys.stderr)
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, _read_prompt)
        if not line:
            break
        if tokenizer is not None:
            token_ids = tokenizer.encode(line)
            stream = tokenizer.stream(token_ids)
        else:
            token_ids = [int(x) for x in line.split()]
            stream = None
        req = {"token_ids": token_ids, "max_tokens": args.max_tokens,
               "temperature": args.temperature}
        async for out in engine.generate(req, Context()):
            for t in out.get("token_ids", []):
                text = stream.push([t]) if stream is not None else f" {t}"
                print(text, end="", flush=True)
        if stream is not None:
            print(stream.flush(), end="")
        print()
    await engine.stop()


def _read_prompt() -> str:
    try:
        return input("> ").strip()
    except EOFError:
        return ""


async def run_batch(engine, tokenizer, args, path: str) -> None:
    """JSONL prompts in → JSONL completions out (ref: Input::Batch)."""
    await engine.start()
    rows = []
    with open(path) as f:
        for line in f:
            if line.strip():
                rows.append(json.loads(line))

    async def one(i, row):
        if "token_ids" in row:
            token_ids = row["token_ids"]
        elif tokenizer is not None:
            token_ids = tokenizer.encode(row.get("prompt", ""))
        else:
            raise ValueError(f"row {i}: no token_ids and no tokenizer")
        req = {"token_ids": token_ids,
               "max_tokens": row.get("max_tokens", args.max_tokens),
               "temperature": row.get("temperature", args.temperature)}
        out_tokens = []
        t0 = time.perf_counter()
        async for out in engine.generate(req, Context()):
            out_tokens.extend(out.get("token_ids", []))
        text = tokenizer.decode(out_tokens) if tokenizer else None
        return {"index": i, "prompt_tokens": len(token_ids),
                "completion_tokens": len(out_tokens),
                "token_ids": out_tokens, "text": text,
                "latency_s": round(time.perf_counter() - t0, 4)}

    results = await asyncio.gather(
        *(one(i, row) for i, row in enumerate(rows))
    )
    for r in results:
        print(json.dumps(r))
    await engine.stop()


async def run_http(engine, tokenizer, args) -> None:
    """OpenAI frontend over an in-process engine — the no-cluster quickstart
    (ref: dynamo-run in=http out=<local engine>)."""
    from .frontend.service import HttpService, ModelEntry, ModelManager
    from .llm.entrypoint import build_local_pipeline

    await engine.start()
    if tokenizer is None:
        raise SystemExit("in=http needs --tokenizer or --weights")
    name = args.model_name or args.model
    pipeline = build_local_pipeline(
        engine, tokenizer, model_name=name,
        max_context_len=args.max_model_len,
    )
    manager = ModelManager()
    manager.register(ModelEntry(name=name, engine=pipeline))
    service = HttpService(manager, host=args.host, port=args.port)
    await service.start()
    log.info("serving %s on %s:%d", name, args.host, service.port)
    try:
        await asyncio.Event().wait()
    finally:
        await service.stop()
        await engine.stop()


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="dynamo-tpu single-command launcher",
        usage="python -m dynamo_tpu.run in=<http|text|batch:FILE> "
              "out=<engine|mocker|echo> [options]",
    )
    p.add_argument("io", nargs=2, metavar="in=/out=",
                   help="in=http|text|batch:FILE and out=engine|mocker|echo")
    p.add_argument("--model", default="tiny", choices=sorted(MODEL_PRESETS))
    p.add_argument("--model-name", default=None)
    p.add_argument("--weights", default=None)
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--mesh", default="1,1")
    p.add_argument("--num-blocks", type=int, default=2048)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-model-len", type=int, default=8192)
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    args = p.parse_args(argv)
    spec = {}
    for part in args.io:
        k, _, v = part.partition("=")
        spec[k] = v
    if "in" not in spec or "out" not in spec:
        p.error("both in= and out= are required")
    args.inp, args.out = spec["in"], spec["out"]
    if args.out not in ("engine", "mocker", "echo"):
        p.error(f"unknown out={args.out}")
    return args


def main(argv=None) -> None:
    args = parse_args(argv)
    if args.out == "engine":  # the only output that compiles
        from .utils.device_env import configure_compile_cache

        configure_compile_cache()
    engine = build_output(args)
    tokenizer = build_tokenizer(args)
    if args.inp == "text":
        asyncio.run(run_text(engine, tokenizer, args))
    elif args.inp.startswith("batch:"):
        asyncio.run(run_batch(engine, tokenizer, args,
                              args.inp.split(":", 1)[1]))
    elif args.inp == "http":
        asyncio.run(run_http(engine, tokenizer, args))
    else:
        raise SystemExit(f"unknown in={args.inp}")


if __name__ == "__main__":
    main()
