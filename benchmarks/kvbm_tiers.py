"""Microbenchmark: the KVBM tier perf story — TTFT for one prompt served
by (a) cold prefill recompute, (b) G2 host-pool onboarding, (c) G4
cluster-store onboarding (ref capability: block_manager CacheLevel G1-G4,
lib/llm/src/block_manager/block_manager.rs:62-76 — the reference sells
tiering as "restore faster than recompute"; this prints the measured
ratio for OUR tiers).

Prints ONE JSON line:
  {"recompute_ms": ..., "g2_ms": ..., "g4_ms": ...,
   "g2_speedup": ..., "g4_speedup": ..., "prompt_tokens": ...}

CPU (tiny model) under ``JAX_PLATFORMS=cpu``; on TPU uses Llama-1B shapes.

Round 5, before PR 1 (2000-token prompt, 1B, v5e): recompute 1.82 s, G2
onboard 2.96 s (0.62x), G4 onboard 11.4 s (0.16x) — restoring ~64 MB of KV
at ~15 ms per upload lost to recomputing 1B-model prefill FLOPs. Those
upload costs were measured on an earlier transport; re-measured by
chip_smoke.py, see CHANGES — this benchmark has not been re-run since.
The crossover favors tiers as recompute scales with model size (a 70B
prefill costs ~56x the FLOPs; the KV bytes per token grow only ~8x), which
is the regime the reference's G2/G3/G4 story targets.
"""

from __future__ import annotations

import asyncio
import json
import os
import time

import jax

from dynamo_tpu.engine.config import EngineConfig, ModelConfig   # noqa: E402
from dynamo_tpu.engine.engine import InferenceEngine, Request    # noqa: E402
from dynamo_tpu.kvbm.manager import KvbmConfig, StoreRemoteTier  # noqa: E402
from dynamo_tpu.runtime.store import StoreClient, StoreServer    # noqa: E402


def _shapes():
    if jax.devices()[0].platform == "tpu":
        return (
            ModelConfig.llama3_1b(),
            EngineConfig(num_blocks=2048, max_model_len=4096,
                         max_num_batched_tokens=2048,
                         prefill_buckets=(2048,), decode_buckets=(8,),
                         max_num_seqs=8),
            2000,
        )
    return (
        ModelConfig.tiny(vocab_size=256),
        EngineConfig(num_blocks=256, block_size=4, max_model_len=512,
                     max_num_batched_tokens=256, prefill_buckets=(256,),
                     decode_buckets=(4,), max_num_seqs=4),
        200,
    )


def _engine(model_cfg, eng_cfg, remote=None, host_blocks=4096):
    eng = InferenceEngine(model_cfg, eng_cfg, seed=0)
    eng.attach_kvbm(KvbmConfig(host_blocks=host_blocks), remote=remote)
    return eng


async def _ttft(engine, prompt) -> float:
    t0 = time.monotonic()
    ttft = None
    async for out in engine.submit(Request(
        request_id=f"bench-{time.monotonic_ns()}",
        token_ids=list(prompt), max_tokens=2, ignore_eos=True,
    )):
        if ttft is None:
            ttft = time.monotonic() - t0
    assert ttft is not None
    return ttft


async def _drain_offload(engine, want: int) -> None:
    for _ in range(200):
        if engine.kvbm.stats.offloaded_blocks >= want:
            return
        await asyncio.sleep(0.05)
    raise RuntimeError("offload drain did not reach %d blocks" % want)


async def main() -> dict:
    model_cfg, eng_cfg, n_prompt = _shapes()
    prompt = [1 + (i * 7) % (model_cfg.vocab_size - 2)
              for i in range(n_prompt)]
    want = n_prompt // eng_cfg.block_size - 1

    server = StoreServer(host="127.0.0.1", port=0)
    await server.start()
    client = await StoreClient.connect(f"127.0.0.1:{server.port}")
    try:
        remote = StoreRemoteTier(client, namespace="bench")

        # warm an engine, offload through the tiers, and measure a cold
        # recompute TTFT on it first (compile cost amortised by a warmup
        # request on a DIFFERENT prompt)
        e1 = _engine(model_cfg, eng_cfg, remote=remote)
        await _ttft(e1, [2 + i % 97 for i in range(n_prompt)])  # compile
        recompute_ms = (await _ttft(e1, prompt)) * 1e3
        await _drain_offload(e1, want)
        await e1.stop()

        # fresh engine sharing the host pool? G2 is per-engine — reuse the
        # SAME engine with G1 cleared instead: evict via clear, onboard
        # from its host pool
        e2 = _engine(model_cfg, eng_cfg, remote=remote)
        await _ttft(e2, [3 + i % 89 for i in range(n_prompt)])  # compile
        first = await _ttft(e2, prompt)
        del first
        await _drain_offload(e2, want)
        e2.clear_kv_blocks()            # drop G1 — prefix must come from G2
        g2_ms = (await _ttft(e2, prompt)) * 1e3
        g2_hits = e2.kvbm.stats.onboarded_blocks
        await e2.stop()

        # a brand-new engine with empty G1+G2: prefix comes from the G4
        # store tier populated by e1/e2
        e3 = _engine(model_cfg, eng_cfg, remote=remote)
        await _ttft(e3, [5 + i % 83 for i in range(n_prompt)])  # compile
        g4_ms = (await _ttft(e3, prompt)) * 1e3
        g4_hits = e3.kvbm.stats.g4_hits
        await e3.stop()
    finally:
        await client.close()
        await server.stop()

    return {
        "recompute_ms": round(recompute_ms, 1),
        "g2_ms": round(g2_ms, 1),
        "g4_ms": round(g4_ms, 1),
        "g2_speedup": round(recompute_ms / max(g2_ms, 1e-9), 2),
        "g4_speedup": round(recompute_ms / max(g4_ms, 1e-9), 2),
        "g2_onboarded_blocks": int(g2_hits),
        "g4_hit_blocks": int(g4_hits),
        "prompt_tokens": n_prompt,
        "platform": jax.devices()[0].platform,
    }


if __name__ == "__main__":
    print(json.dumps(asyncio.run(main())))
