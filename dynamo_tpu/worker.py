"""Engine worker process: build the JAX engine, serve it, register the model.

Role-equivalent to the reference's backend worker mains (ref: components/
backends/vllm/src/dynamo/vllm/main.py:184,325): create the runtime, start the
inference engine, expose ``generate`` (+ ``clear_kv_blocks``) endpoints, and
register the model so frontends discover it.

    python -m dynamo_tpu.worker --model tiny --model-name demo \
        --tokenizer /path/tokenizer.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
from typing import Optional

from .engine.config import EngineConfig, ModelConfig
from .engine.engine import InferenceEngine
from .llm.tokenizer import Tokenizer
from .runtime.component import DistributedRuntime
from .serving import ServeOptions, load_tokenizer, run_until_shutdown, serve_engine
from .utils.config import RuntimeConfig
from .utils.device_env import configure_compile_cache
from .utils.logging import get_logger

log = get_logger("worker")

MODEL_PRESETS = {
    "tiny": ModelConfig.tiny,
    "1b": ModelConfig.llama3_1b,
    "8b": ModelConfig.llama3_8b,
    "70b": ModelConfig.llama3_70b,
}


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="dynamo-tpu engine worker")
    p.add_argument("--model", default="tiny", choices=sorted(MODEL_PRESETS))
    p.add_argument("--model-name", default=None,
                   help="served model name (default: preset name)")
    p.add_argument("--tokenizer", default=None,
                   help="tokenizer.json path or HF model dir")
    p.add_argument("--tool-call-parser", default=None,
                   choices=["hermes", "json", "pythonic"],
                   help="streaming tool-call parser advertised in the MDC")
    p.add_argument("--reasoning-parser", default=None,
                   help="set to split <think>…</think> into "
                        "reasoning_content (e.g. 'think')")
    p.add_argument("--weights", default=None,
                   help="HF checkpoint dir (*.safetensors [+ config.json, "
                        "which overrides --model]; tokenizer defaults to "
                        "the same dir)")
    p.add_argument("--store-addr", default=None)
    p.add_argument("--namespace", default=None)
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=2048)
    p.add_argument("--max-num-seqs", type=int, default=64)
    p.add_argument("--max-batched-tokens", type=int, default=512,
                   help="prompt tokens one round may prefill, in chunks of "
                        "at most the largest prefill bucket; decode rows "
                        "take none of it and are bounded by --max-num-seqs")
    p.add_argument("--max-model-len", type=int, default=8192)
    p.add_argument("--mesh", default="1,1", help="dp,tp mesh axis sizes")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel stages (layers stage-sharded, "
                        "GPipe-microbatched decode; exclusive with --mesh)")
    p.add_argument("--pp-microbatches", type=int, default=4)
    p.add_argument("--migration-limit", type=int, default=3)
    p.add_argument("--spec-mode", default=None, choices=["off", "ngram"],
                   help="speculative decoding: device-side n-gram drafting "
                        "+ batched verify (default: DYNTPU_SPEC_MODE, off)")
    p.add_argument("--spec-k", type=int, default=None,
                   help="max draft tokens verified per window "
                        "(default: DYNTPU_SPEC_K, 4)")
    p.add_argument("--attention-impl", default="pallas",
                   choices=["pallas", "einsum"],
                   help="decode attention path: the Pallas paged kernel or "
                        "the XLA gathered-einsum reference")
    p.add_argument("--weight-dtype", default=None,
                   choices=["bf16", "int8", "fp8"],
                   help="weight storage dtype: int8/fp8 quantize at load "
                        "time with per-channel scales "
                        "(default: DYNTPU_WEIGHT_DTYPE, bf16)")
    p.add_argument("--kv-dtype", default=None,
                   choices=["bf16", "int8", "fp8"],
                   help="paged-KV storage dtype: int8/fp8 halve KV bytes "
                        "per token with per-token scales "
                        "(default: DYNTPU_KV_DTYPE, bf16)")
    p.add_argument("--prefill-chunk-tokens", type=int, default=None,
                   help="cap each prefill chunk at this many tokens so long "
                        "prompts interleave with running decodes instead of "
                        "stalling them (0 = whole-bucket prefill; default: "
                        "DYNTPU_PREFILL_CHUNK_TOKENS, 0)")
    p.add_argument("--drain-timeout", type=float, default=None,
                   help="seconds in-flight streams get to finish on graceful "
                        "drain before being stopped for client migration "
                        "(default: DYNTPU_DRAIN_TIMEOUT_S, 30)")
    p.add_argument("--advertise-host", default="127.0.0.1")
    p.add_argument(
        "--disagg-mode", default="agg", choices=["agg", "decode", "prefill"],
        help="aggregated, decode-orchestrator, or prefill worker "
             "(ref: disagg_serving.md)",
    )
    p.add_argument("--prefill-component", default="prefill")
    p.add_argument("--min-remote-prefill-tokens", type=int, default=32)
    p.add_argument(
        "--disagg-queue", action="store_true",
        help="queue-based disagg: decode q_pushes prefill work onto the "
             "store work queue, prefill workers q_pop (ref: the JetStream "
             "prefill queue); default is direct round-robin push",
    )
    p.add_argument("--disagg-queue-name", default="prefill_queue")
    p.add_argument("--kvbm-host-blocks", type=int, default=0,
                   help="G2 host-tier capacity in blocks (0 = KVBM off)")
    p.add_argument("--kvbm-host-bytes", type=int, default=0,
                   help="G2 host-tier capacity in bytes (0 = unbounded); "
                        "byte-bounding lets a quantized (int8/fp8) KV "
                        "cache hold ~2x the blocks in the same budget")
    p.add_argument("--kvbm-disk-dir", default=None)
    p.add_argument("--kvbm-disk-blocks", type=int, default=0)
    p.add_argument("--kvbm-remote", action="store_true",
                   help="enable the G4 cluster-shared tier in the store")
    p.add_argument("--kvbm-distributed", action="store_true",
                   help="share the G2 host tier across workers (presence "
                        "keys in the store + direct TCP block fetch; ref: "
                        "block_manager/distributed)")
    p.add_argument("--kvbm-group", default=None,
                   help="distributed-KVBM group name for barrier bring-up")
    p.add_argument("--kvbm-group-role", choices=["leader", "worker"],
                   default="worker")
    p.add_argument("--kvbm-group-size", type=int, default=1,
                   help="worker count the group leader waits for")
    p.add_argument("--mm-encoder", action="store_true",
                   help="serve a colocated vision encode endpoint and "
                        "advertise multimodal support (EPD; a standalone "
                        "encode worker is python -m dynamo_tpu.multimodal)")
    p.add_argument("--mm-image-size", type=int, default=32)
    p.add_argument("--mm-patch-size", type=int, default=8)
    p.add_argument("--mm-encode-component", default=None,
                   help="advertise a REMOTE encode worker's component "
                        "instead of serving one here")
    # multi-host SPMD (one process per host of a slice; flags default to
    # the JAX_* env vars so TPU pod launchers can set them uniformly)
    p.add_argument("--coordinator",
                   default=os.environ.get("JAX_COORDINATOR_ADDRESS"),
                   help="host0 ip:port for jax.distributed (multi-host)")
    p.add_argument("--num-hosts", type=int,
                   default=int(os.environ.get("JAX_PROCESS_COUNT", "1")))
    p.add_argument("--host-index", type=int,
                   default=int(os.environ.get("JAX_PROCESS_INDEX", "0")))
    return p.parse_args(argv)


async def run_worker(args: argparse.Namespace) -> None:
    config = RuntimeConfig.from_settings()
    if args.store_addr:
        config.store_addr = args.store_addr
    if args.namespace:
        config.namespace = args.namespace
    if args.drain_timeout is not None:
        config.drain_timeout_s = args.drain_timeout

    from .parallel.multihost import MultihostConfig, initialize_distributed

    mh = MultihostConfig(
        coordinator=args.coordinator, num_hosts=args.num_hosts,
        host_index=args.host_index,
    )
    # must precede every other JAX call — it decides the backend topology
    initialize_distributed(mh)
    if mh.enabled and (args.disagg_mode != "agg"
                       or args.kvbm_host_blocks > 0):
        raise SystemExit(
            "multi-host workers serve the aggregated path only "
            "(disagg/KVBM are single-host features)"
        )

    dp, tp = (int(x) for x in args.mesh.split(","))
    model_cfg = MODEL_PRESETS[args.model]()
    weight_dtype = (args.weight_dtype if args.weight_dtype is not None
                    else config.weight_dtype)
    kv_dtype = (args.kv_dtype if args.kv_dtype is not None
                else config.kv_dtype)
    params = None
    if args.weights:
        from .engine.weights import (
            load_hf_params, load_hf_params_sharded, model_config_from_hf,
        )

        if os.path.exists(os.path.join(args.weights, "config.json")):
            model_cfg = model_config_from_hf(args.weights)
        if dp * tp > 1 and args.pp <= 1:
            # stream onto device shards (peak host memory = one tensor)
            from .engine import model as model_lib

            mesh = model_lib.make_mesh((dp, tp))
            params = load_hf_params_sharded(
                args.weights, model_cfg, mesh, weight_dtype)
        else:
            params = load_hf_params(args.weights, model_cfg, weight_dtype)
        if args.tokenizer is None:
            args.tokenizer = args.weights
    eng_cfg = EngineConfig(
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        max_num_seqs=args.max_num_seqs,
        max_num_batched_tokens=args.max_batched_tokens,
        max_model_len=min(args.max_model_len, model_cfg.max_position),
        mesh_shape=(dp, tp),
        pp_stages=args.pp,
        pp_microbatches=args.pp_microbatches,
        attention_impl=args.attention_impl,
        prefill_chunk_tokens=(
            args.prefill_chunk_tokens
            if args.prefill_chunk_tokens is not None
            else config.prefill_chunk_tokens
        ),
        spec_mode=(args.spec_mode if args.spec_mode is not None
                   else config.spec_mode),
        spec_k=(args.spec_k if args.spec_k is not None else config.spec_k),
        spec_auto_disable_threshold=config.spec_auto_disable_threshold,
        spec_auto_disable_window=config.spec_auto_disable_window,
        weight_dtype=weight_dtype,
        kv_dtype=kv_dtype,
    )
    tokenizer = load_tokenizer(args.tokenizer)
    name = args.model_name or args.model

    # Build the engine BEFORE taking the store lease: engine construction is
    # seconds of synchronous JAX work (param init, device_put) that would
    # starve the lease keepalive and get the worker evicted at birth.
    engine = InferenceEngine(model_cfg, eng_cfg, params=params)
    runtime = await DistributedRuntime.from_settings(config)

    if mh.enabled and not mh.is_leader:
        # follower: replay the leader's step plans; no serving, no
        # registration — the leader is the slice's single front door
        from .parallel.multihost import follower_loop

        log.info("worker ready: model=%s mode=follower host=%d/%d",
                 name, mh.host_index, mh.num_hosts)
        try:
            await follower_loop(runtime, engine, mh, name,
                                component=args.component)
            log.warning("follower exiting (leader lost)")
        except BaseException:  # dynalint: disable=DT303 — os._exit below
            # the traceback must hit the log BEFORE the hard exit below
            # discards it — a replay bug would otherwise masquerade as
            # endless "leader lost" restarts
            log.exception("follower loop terminated abnormally")
        finally:
            try:
                await asyncio.wait_for(engine.stop(), timeout=10)
                await asyncio.wait_for(runtime.shutdown(), timeout=10)
            except BaseException:  # dynalint: disable=DT303
                # incl. CancelledError — the hard os._exit(1) below is the
                # contract; nothing may skip it
                log.exception("follower cleanup failed")
            # hard exit: jax.distributed's atexit barrier blocks forever
            # when the coordinator host is gone, and the supervisor's
            # restart contract needs a DEAD process, not a graceful-looking
            # hang
            os._exit(1)
        return

    if mh.enabled:
        # leader: stream every executed step to the followers, and gate
        # model registration on all of them being connected
        from .parallel.multihost import (
            StepBroadcaster, StepStreamHandler, leader_gate,
        )

        broadcaster = StepBroadcaster(asyncio.get_running_loop())
        engine.step_sink = broadcaster.sink
        step_ep = (runtime.namespace().component(args.component)
                   .endpoint("step_stream"))
        await step_ep.serve_endpoint(
            StepStreamHandler(broadcaster,
                              heartbeat_interval_s=mh.heartbeat_interval_s),
            advertise_host=args.advertise_host,
        )
        await leader_gate(runtime.store, mh, broadcaster, name)

    if args.kvbm_host_blocks > 0:
        from .kvbm.manager import KvbmConfig, StoreRemoteTier

        remote = None
        if args.kvbm_remote:
            remote = StoreRemoteTier(
                runtime.store, namespace=config.namespace
            )
        engine.attach_kvbm(KvbmConfig(
            host_blocks=args.kvbm_host_blocks,
            host_bytes=args.kvbm_host_bytes,
            disk_dir=args.kvbm_disk_dir,
            disk_blocks=args.kvbm_disk_blocks,
        ), remote=remote)

    kvbm_dist = None
    if args.kvbm_group:
        # a group member that never starts the presence plane would leave
        # the leader waiting at the barrier for a check-in that never comes
        args.kvbm_distributed = True
    if args.kvbm_distributed and engine.kvbm is None:
        raise SystemExit(
            "--kvbm-distributed/--kvbm-group require KVBM "
            "(--kvbm-host-blocks > 0)"
        )
    if args.kvbm_distributed:
        from .kvbm.distributed import (
            DistributedKvbm, KvbmGroup, engine_layout,
        )

        kvbm_dist = DistributedKvbm(
            engine.kvbm, runtime.store, runtime.primary_lease,
            namespace=config.namespace, advertise_host=args.advertise_host,
            scope=name,
        )
        await kvbm_dist.start()
        if args.kvbm_group:
            layout = engine_layout(engine)
            if args.kvbm_group_role == "leader":
                await KvbmGroup.lead(
                    runtime.store, args.kvbm_group, args.kvbm_group_size,
                    layout,
                )
            else:
                await KvbmGroup.join(
                    runtime.store, args.kvbm_group,
                    f"worker-{runtime.primary_lease}", layout,
                )
            log.info("kvbm group %s formed (%s)", args.kvbm_group,
                     args.kvbm_group_role)

    if config.prefix_enabled:
        from .prefix.manager import PrefixCacheConfig

        # after attach_kvbm so the manager chains the host-pool drop hook
        # and mirrors the G2/G4 tiers; works index-only without KVBM
        engine.attach_prefix_cache(
            config=PrefixCacheConfig(
                evict_to_host_blocks=config.prefix_evict_blocks,
                tier_weight_g2=config.prefix_tier_weight_g2,
                tier_weight_g4=config.prefix_tier_weight_g4,
            ),
            worker_id=runtime.primary_lease,
        )

    handler = None
    queue_worker = None
    component = args.component
    if args.disagg_mode == "prefill":
        from .disagg import DisaggConfig, PrefillHandler, PrefillQueueWorker

        # prefill workers serve on their own component; decode workers own
        # model registration (ref: vllm main.py:137 init_prefill)
        component = args.prefill_component
        handler = PrefillHandler(
            engine, config=DisaggConfig.from_runtime(config)
        )
        handler.start_orphan_sweeper()
        if args.disagg_queue:
            queue_worker = PrefillQueueWorker(
                handler, runtime.store, queue_name=args.disagg_queue_name
            )
            queue_worker.start()
        tokenizer = None
    elif args.disagg_mode == "decode":
        from .disagg import DecodeHandler, DisaggConfig

        prefill_client = await (
            runtime.namespace().component(args.prefill_component)
            .endpoint("generate").client()
        )
        handler = DecodeHandler(
            engine, prefill_client,
            DisaggConfig.from_runtime(
                config,
                min_remote_prefill_tokens=args.min_remote_prefill_tokens,
                use_queue=args.disagg_queue,
                queue_name=args.disagg_queue_name,
            ),
            store=runtime.store,
        )
        handler.start_orphan_sweeper()

    mm_opts = None
    mm_handler = None
    if args.mm_encoder or args.mm_encode_component:
        from .multimodal import (
            EncodeHandler, VisionEncoder, VisionEncoderConfig,
        )

        vcfg = VisionEncoderConfig(
            image_size=args.mm_image_size, patch_size=args.mm_patch_size,
            model_dim=model_cfg.hidden_size,
        )
        mm_opts = {
            "tokens_per_image": vcfg.tokens_per_image,
            "image_size": vcfg.image_size,
            "component": args.mm_encode_component or component,
            "endpoint": "encode",
        }
        if not args.mm_encode_component:
            mm_handler = EncodeHandler(VisionEncoder(vcfg))

    opts = ServeOptions(
        name=name, component=component, endpoint=args.endpoint,
        advertise_host=args.advertise_host,
        migration_limit=args.migration_limit,
        tool_call_parser=args.tool_call_parser,
        reasoning_parser=args.reasoning_parser,
        mm=mm_opts, mm_handler=mm_handler,
    )
    served, kv_pub, metrics_pub = await serve_engine(
        runtime, engine, eng_cfg, opts, tokenizer, handler=handler
    )
    if args.disagg_mode in ("prefill", "decode"):
        # surface the disagg health gauges (fallbacks, breaker state,
        # retries, orphan reaps — and in queue mode the prefill backlog)
        # to the planner via load metrics
        metrics_pub.extra_fn = handler.metrics_extra
    if args.disagg_mode == "decode" and args.disagg_queue:
        handler.start_depth_monitor()
    if args.disagg_mode == "decode":
        inject_ep = (runtime.namespace().component(component)
                     .endpoint("kv_inject"))
        inject_served = await inject_ep.serve_endpoint(
            handler.inject_handler(), advertise_host=args.advertise_host
        )
        handler.kv_inject_addr = inject_served.instance.addr

    report = engine.device_report()
    if runtime.system_server is not None:
        # GET /health → probes.engine: device, traced attention, compile
        # and memory counters, live
        runtime.system_server.register_probe(
            "engine", lambda: {"healthy": True, **engine.device_report()}
        )
    log.info(
        "worker ready: model=%s mode=%s device=%s native=%s "
        "compile_cache=%s engine=%s",
        name, args.disagg_mode,
        json.dumps({"platform": report["platform"],
                    "kind": report["device_kind"],
                    "count": report["device_count"]}),
        report["native"], report["compile_cache"]["dir"], eng_cfg,
    )
    try:
        await run_until_shutdown(runtime, engine, served, kv_pub,
                                 metrics_pub)
    finally:
        if queue_worker is not None:
            await queue_worker.stop()
        if kvbm_dist is not None:
            await kvbm_dist.stop()
        if hasattr(handler, "close"):
            handler.close()


def main(argv=None) -> None:
    args = parse_args(argv)
    configure_compile_cache()  # before anything compiles
    asyncio.run(run_worker(args))


if __name__ == "__main__":
    main()
