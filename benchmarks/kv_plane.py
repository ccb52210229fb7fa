"""Microbenchmark: KV-block transfer over the host relay vs the device
plane (ref capability: NIXL device-to-device vs bounce-buffer fallback,
docs/architecture/disagg_serving.md §Efficient KV Transfer).

Prints ONE JSON line:
  {"relay_gbps": ..., "device_gbps": ..., "speedup": ..., "bytes": ...}

Runs on whatever backend jax initialises (CPU fallback via
``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import asyncio
import json
import os
import time

import jax

from dynamo_tpu.disagg.ici import DevicePlane            # noqa: E402
from dynamo_tpu.disagg.protocol import kv_from_wire, kv_to_wire  # noqa: E402
from dynamo_tpu.engine.config import EngineConfig, ModelConfig   # noqa: E402
from dynamo_tpu.engine.engine import InferenceEngine, Request    # noqa: E402


async def main() -> dict:
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        model = ModelConfig.llama3_1b()
        eng = EngineConfig(
            num_blocks=2048, max_model_len=4096,
            max_num_batched_tokens=2048, prefill_buckets=(2048,),
            decode_buckets=(8,), max_num_seqs=8,
        )
        prompt_len = 2000
    else:
        model = ModelConfig.tiny(vocab_size=256)
        eng = EngineConfig(
            num_blocks=256, block_size=16, max_model_len=2048,
            max_num_batched_tokens=2048, prefill_buckets=(2048,),
            decode_buckets=(8,), max_num_seqs=8,
        )
        prompt_len = 1500

    src = InferenceEngine(model, eng)
    dst = InferenceEngine(model, eng, seed=1)
    plane = DevicePlane()

    prompt = [1 + (i % (model.vocab_size - 1)) for i in range(prompt_len)]
    seq, _ = await src.prefill_held(
        Request(request_id="s", token_ids=prompt, max_tokens=1)
    )
    dseq = dst.reserve_sequence(
        Request(request_id="d", token_ids=prompt, max_tokens=4)
    )
    assert dseq is not None
    src_ids, dst_ids = list(seq.block_table), list(dseq.block_table)

    reps = int(os.environ.get("KV_BENCH_REPS", 10))

    # warm both paths (compiles)
    data = await src.extract_kv(seq)
    await dst.inject_kv(dseq, kv_from_wire(kv_to_wire(data)))
    await plane.transfer(src, src_ids, dst, dst_ids)
    nbytes = 2 * data["k"].size * data["k"].dtype.itemsize

    t0 = time.monotonic()
    for _ in range(reps):
        data = await src.extract_kv(seq)
        wire = kv_to_wire(data)
        await dst.inject_kv(dseq, kv_from_wire(wire))
    relay_s = (time.monotonic() - t0) / reps

    t0 = time.monotonic()
    for _ in range(reps):
        await plane.transfer(src, src_ids, dst, dst_ids)
    jax.block_until_ready(dst.cache["k"][0])
    device_s = (time.monotonic() - t0) / reps

    src.release_held(seq)
    dst.cancel_reservation(dseq)
    await src.stop()
    await dst.stop()

    return {
        "metric": "KV P->D transfer bandwidth, device plane vs host relay",
        "bytes": nbytes,
        "blocks": len(src_ids),
        "relay_gbps": round(nbytes / relay_s / 1e9, 4),
        "device_gbps": round(nbytes / device_s / 1e9, 4),
        "speedup": round(relay_s / device_s, 2),
        "platform": jax.devices()[0].platform,
    }


if __name__ == "__main__":
    print(json.dumps(asyncio.run(main())))
