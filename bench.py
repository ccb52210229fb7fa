"""Serving throughput benchmark — BASELINE config #1 (aggregated, 1 chip).

Drives the continuous-batching JAX engine with a genai-perf-shaped closed
loop (fixed concurrency, fixed ISL/OSL, greedy decode — the reference recipe
shape from recipes/llama-3-70b/vllm/disagg-single-node/perf.yaml scaled to
one chip) and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "tok/s/chip", "vs_baseline": N, ...}

``vs_baseline`` is measured output-token throughput divided by a GPU-parity
target for the same model class on one accelerator (vLLM Llama-3.2-1B-class
on A100: ~1e4 output tok/s at concurrency 64 — the parity bar BASELINE.md
sets). Extra keys carry TTFT/ITL percentiles, an MFU estimate, and (on TPU)
a Pallas paged-attention kernel-vs-einsum correctness + speedup check.

Contract: the measured run needs a chip. The child process is probe AND
bench in one: it prints ``PROBE|platform|kind`` the moment ``jax.devices()``
returns, then runs the measured loop and prints the JSON. The parent never
touches JAX (the chip belongs to the child); it streams the child's stdout
with two deadlines (backend-init and bench), retries a failed attempt, arms
``faulthandler`` in the child so a hang leaves a thread dump on stderr, and
on failure — or when the child came up on anything but a TPU — writes the
attempts' stderr tails to stderr and exits non-zero with NO JSON line.
The compile cache is placed by ``dynamo_tpu.utils.device_env``
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``).

Env overrides: BENCH_ISL, BENCH_OSL, BENCH_CONCURRENCY, BENCH_REQUESTS,
BENCH_MODEL (tiny|1b), BENCH_PROBE_TIMEOUT (default 600), BENCH_TIMEOUT
(default 2400), BENCH_PROBE_RETRIES (default 2),
BENCH_DECODE_STEPS (autopilot window length; default 1 on TPU — in-program
step chains defeat XLA cache aliasing), BENCH_PIPELINE_DEPTH (run-ahead
windows in flight; default 16 on TPU), BENCH_BLOCK_LOOKAHEAD (blocks
reserved ahead per seq; default 8 on TPU), BENCH_SPEC_MODE (off|ngram —
speculative decoding; default off), BENCH_SPEC_K (draft tokens per verify
window; default 4), BENCH_ATTENTION_IMPL (pallas|einsum|auto; "auto" probes
Pallas vs einsum per shape class at startup and reports the choices +
ratios), BENCH_PREFILL_CHUNK_TOKENS (chunked prefill: per-chunk token cap
so long prompts interleave with decode; default 0 = whole-bucket prefill),
BENCH_WEIGHT_DTYPE / BENCH_KV_DTYPE (bf16|int8|fp8 — quantized serving:
int8/fp8 weights with per-channel scales and/or a quantized paged KV cache;
MFU is reported against the matching int8/fp8 roofline, default bf16).

ITL reporting: per-token client arrival timestamps, with bursts (several
tokens landing within ITL_BURST_EPS_S of each other, e.g. one spec verify
window) amortised evenly over the burst gap — itl_p50/p99/mean_ms reflect
stream pacing, not raw inter-arrival deltas that read 0 inside a burst.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import tempfile
import threading
import time

# GPU-parity bars: output tok/s per accelerator for each model class under
# vLLM-class continuous-batching serving at the BASELINE load shapes —
# the denominators for ``vs_baseline``. Derivation (public serving figures,
# order-of-magnitude calibrated against vLLM benchmark blogs and the
# reference's recipe hardware):
#   1b:  Llama-3.2-1B-class on one A100, conc 64, short ISL ≈ 1e4 out tok/s
#   8b:  Llama-3-8B on one A100/H100, conc 64 ≈ 2.5e3 out tok/s
#   70b: Llama-3.3-70B FP8 on one 8xH100 node (recipes/llama-3-70b/vllm),
#        ISL 8192 / OSL 1024 / conc 64 ≈ 450 out tok/s PER GPU
# "tiny" is a CPU smoke model; it inherits the 1b bar so its vs_baseline
# stays an honest ~0.
GPU_PARITY_TOKS = {
    "tiny": 10_000.0,
    "1b": 10_000.0,
    "8b": 2_500.0,
    "70b": 450.0,
}

# Peak FLOP/s per chip and the analytic FLOPs model live in
# dynamo_tpu.observability.flops — ONE model shared with the engine's
# flight recorder, so bench MFU and the live engine_mfu gauge agree.


def _peak_flops(device_kind: str, platform: str,
                dtype: str = "bfloat16") -> float:
    from dynamo_tpu.observability.flops import peak_flops

    return peak_flops(device_kind, platform, dtype)


def _pct(values, q):
    if not values:
        return 0.0
    values = sorted(values)
    idx = min(len(values) - 1, int(round(q / 100.0 * (len(values) - 1))))
    return values[idx]


# ------------------------------ child side --------------------------------


def _kernel_check_class(B: int, T: int, spec_k: int = 4,
                        tile=(0, 0)) -> dict:
    """Ragged Pallas paged-attention vs the gathered-einsum path on one
    shape class: numerical max-abs-err + timed speedup on the real
    backend. All rows attend a full 512-token context of which the chunk
    is the last T tokens. ``tile`` is the autotuned (q_tile, kv_tile) for
    the class — (0, 0) times the kernel defaults."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import model as model_lib
    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode, paged_attention_ragged,
    )

    H, KV, hd = 16, 8, 128
    bs, W = 16, 32                      # 512-token contexts
    NB = 1 + B * W
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), dt)
    k = jnp.asarray(rng.standard_normal((NB, KV, bs, hd)), dt)
    v = jnp.asarray(rng.standard_normal((NB, KV, bs, hd)), dt)
    tables = jnp.asarray(1 + np.arange(B * W).reshape(B, W), jnp.int32)
    seq_lens = jnp.full((B,), W * bs, jnp.int32)

    interpret = jax.default_backend() != "tpu"
    q_tile, kv_tile = int(tile[0]), int(tile[1])
    if q_tile > 0 and T % q_tile:
        q_tile = 0  # tuned for a different chunk length — use the default
    if kv_tile > 0 and bs % kv_tile and kv_tile % bs:
        kv_tile = 0
    if T == 1:
        decode = jax.jit(functools.partial(
            paged_attention_decode, block_size=bs, kv_tile=kv_tile,
            interpret=interpret,
        ))

        def kernel(q, kc, vc, tables, lens):
            return decode(q[:, 0], kc, vc, tables, lens)[:, None]
    else:
        q_start = jnp.arange(B + 1, dtype=jnp.int32) * T
        q_lens = jnp.full((B,), T, jnp.int32)
        ragged = jax.jit(functools.partial(
            paged_attention_ragged, block_size=bs, max_q_len=T,
            q_tile=q_tile, kv_tile=kv_tile,
            interpret=interpret,
        ))

        def kernel(q, kc, vc, tables, lens):
            out = ragged(q.reshape(B * T, H, hd), kc, vc, tables,
                         q_start, q_lens, lens)
            return out.reshape(B, T, H, hd)

    kernel = jax.jit(kernel)

    @jax.jit
    def einsum_path(q, kc, vc, tables, lens):
        k_all = jnp.take(kc, tables.reshape(-1), axis=0).reshape(
            B, W, KV, bs, hd
        ).transpose(0, 1, 3, 2, 4).reshape(B, W * bs, KV, hd)
        v_all = jnp.take(vc, tables.reshape(-1), axis=0).reshape(
            B, W, KV, bs, hd
        ).transpose(0, 1, 3, 2, 4).reshape(B, W * bs, KV, hd)
        pos = (lens[:, None] - T) + jnp.arange(T)[None, :]
        return model_lib._attention(q, k_all, v_all, pos)

    out_k = jax.device_get(kernel(q, k, v, tables, seq_lens))
    out_r = jax.device_get(einsum_path(q, k, v, tables, seq_lens))
    err = float(np.max(np.abs(
        out_k.astype(np.float32) - out_r.astype(np.float32)
    )))

    def timeit(fn, iters=30):
        fn(q, k, v, tables, seq_lens).block_until_ready()  # warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k, v, tables, seq_lens)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e3

    kernel_ms = timeit(kernel)
    einsum_ms = timeit(einsum_path)
    return {
        "max_abs_err": err,
        "kernel_ms": round(kernel_ms, 3),
        "einsum_ms": round(einsum_ms, 3),
        "speedup": round(einsum_ms / max(kernel_ms, 1e-9), 2),
        "interpret": interpret,
    }


def _kernel_check(spec_k: int = 4, tiles=None) -> dict:
    """Probe the ragged kernel on the three serving shape classes (decode
    rows, spec [B, k+1] verify windows, prefill chunks); flat keys ride the
    bench JSON. ``kernel_speedup`` / ``kernel_ms`` keep their historical
    decode-class meaning; ``kernel_max_abs_err`` is the worst class.
    ``tiles`` maps class -> autotuned (q_tile, kv_tile) so the reported
    speedups time the configuration that actually serves."""
    classes = {
        "decode": (32, 1),
        "spec": (32, spec_k + 1),
        "prefill": (4, 256),
    }
    tiles = tiles or {}
    out: dict = {"kernel_max_abs_err": 0.0}
    for name, (B, T) in classes.items():
        info = _kernel_check_class(B, T, spec_k,
                                   tile=tiles.get(name, (0, 0)))
        out[f"kernel_speedup_{name}"] = info["speedup"]
        out[f"kernel_ms_{name}"] = info["kernel_ms"]
        out[f"einsum_ms_{name}"] = info["einsum_ms"]
        out["kernel_max_abs_err"] = round(
            max(out["kernel_max_abs_err"], info["max_abs_err"]), 5
        )
        out["kernel_interpret"] = info["interpret"]
    out["kernel_ms"] = out["kernel_ms_decode"]
    out["einsum_ms"] = out["einsum_ms_decode"]
    out["kernel_speedup"] = out["kernel_speedup_decode"]
    return out


# Client arrivals within this window belong to one burst: a single fetch
# window (spec verify, decode_steps > 1) lands several tokens back-to-back,
# and raw inter-arrival deltas would record them as ~0 ms ITLs — the
# itl_p50_ms: 0.0 artifact. Amortising the burst's gap evenly over its
# tokens reports the latency a reader of the stream actually experiences.
ITL_BURST_EPS_S = 5e-4


def _itl_samples(ts: list) -> list:
    """Per-token ITL samples from one request's arrival timestamps.

    Splits arrivals into bursts (consecutive deltas <= ITL_BURST_EPS_S);
    a burst of m tokens arriving gap g after the previous burst yields m
    samples of g/m, so sum(samples) matches the request's decode wall
    time to within the sub-eps intra-burst deltas and percentiles
    reflect real stream pacing."""
    samples: list = []
    i = 1
    while i < len(ts):
        gap = ts[i] - ts[i - 1]
        j = i + 1
        while j < len(ts) and ts[j] - ts[j - 1] <= ITL_BURST_EPS_S:
            j += 1
        m = j - i
        samples.extend([gap / m] * m)
        i = j
    return samples


async def run_bench() -> dict:
    import faulthandler

    # A hang anywhere (backend init, first compile, a stuck collective)
    # leaves periodic thread dumps on stderr for the parent to report.
    faulthandler.dump_traceback_later(240, repeat=True, file=sys.stderr)

    import jax

    from dynamo_tpu.utils.device_env import configure_compile_cache

    configure_compile_cache()

    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.engine import InferenceEngine, Request

    t_init0 = time.monotonic()
    dev = jax.devices()[0]
    backend_init_s = time.monotonic() - t_init0
    platform = dev.platform
    on_tpu = platform == "tpu"
    # handshake: the parent's probe deadline keys off this line
    print("PROBE|" + platform + "|" + getattr(dev, "device_kind", ""),
          flush=True)

    # model presets: (config factory, default ISL/OSL/conc/requests,
    # engine shape). The "baseline" profile (BENCH_PROFILE=baseline) runs
    # the reference recipe load shape — ISL 8192 / OSL 1024 / conc 64 /
    # 320 requests (recipes/llama-3-70b/vllm/disagg-single-node/
    # perf.yaml:41-50) — on any model preset that fits the chip.
    model_name = os.environ.get("BENCH_MODEL", "1b" if on_tpu else "tiny")
    baseline_profile = os.environ.get("BENCH_PROFILE") == "baseline"
    # Pipelined serving knobs. Their premise — a host sync ~64 ms and each
    # fresh host->device upload ~15 ms against a ~3 ms chained 1B decode
    # step and a 0.3 ms enqueue — was measured on an earlier transport;
    # re-measured by chip_smoke.py, see CHANGES. Under that premise decode
    # runs K=1 autopilot windows (device-
    # resident control state, zero uploads steady-state) under a deep
    # run-ahead pipeline with grouped fetches. K>1 in-program windows are
    # NOT faster here: XLA cannot keep the paged cache in place through
    # an in-program step chain (~30x slowdown measured), so K stays 1.
    decode_steps = int(os.environ.get(
        "BENCH_DECODE_STEPS", 1 if on_tpu else 4))
    pipe_depth = int(os.environ.get(
        "BENCH_PIPELINE_DEPTH", 16 if on_tpu else 2))
    lookahead = int(os.environ.get(
        "BENCH_BLOCK_LOOKAHEAD", 8 if on_tpu else 0))
    spec_mode = os.environ.get("BENCH_SPEC_MODE", "off")
    spec_k = int(os.environ.get("BENCH_SPEC_K", 4))
    attn_impl = os.environ.get("BENCH_ATTENTION_IMPL", "auto")
    prefill_chunk = int(os.environ.get("BENCH_PREFILL_CHUNK_TOKENS", 0))
    weight_dtype = os.environ.get("BENCH_WEIGHT_DTYPE", "bf16")
    kv_dtype = os.environ.get("BENCH_KV_DTYPE", "bf16")
    spec_kw = dict(spec_mode=spec_mode, spec_k=spec_k,
                   attention_impl=attn_impl,
                   prefill_chunk_tokens=prefill_chunk,
                   weight_dtype=weight_dtype, kv_dtype=kv_dtype)
    if model_name == "tiny":
        model_cfg = ModelConfig.tiny()
        defaults = (64, 16, 8, 24)
        eng_cfg = EngineConfig(
            num_blocks=512, max_model_len=512,
            max_num_batched_tokens=256,
            prefill_buckets=(256,), decode_buckets=(16,), max_num_seqs=16,
            decode_steps=decode_steps, pipeline_depth=pipe_depth,
            block_lookahead=lookahead, **spec_kw,
        )
    elif baseline_profile:
        factory = {"1b": ModelConfig.llama3_1b,
                   "8b": ModelConfig.llama3_8b,
                   "70b": ModelConfig.llama3_70b}[model_name]
        model_cfg = factory()
        defaults = (8192, 1024, 64, 320)
        eng_cfg = None  # built below from the resolved shape
    else:
        factory = {"1b": ModelConfig.llama3_1b,
                   "8b": ModelConfig.llama3_8b,
                   "70b": ModelConfig.llama3_70b}[model_name]
        model_cfg = factory()
        defaults = (512, 128, 64, 192)
        # single prefill/decode bucket each → two XLA programs, no
        # mid-measurement compile stalls
        eng_cfg = EngineConfig(
            num_blocks=8192, max_model_len=1024,
            # budget > bucket: decode seats coexist with a full 512-token
            # prefill chunk instead of splitting prompts 448+64
            max_num_batched_tokens=1024 + 64,
            prefill_buckets=(512, 1024), decode_buckets=(64,),
            max_num_seqs=64,
            decode_steps=decode_steps, pipeline_depth=pipe_depth,
            block_lookahead=lookahead, **spec_kw,
        )
    isl = int(os.environ.get("BENCH_ISL", defaults[0]))
    osl = int(os.environ.get("BENCH_OSL", defaults[1]))
    concurrency = int(os.environ.get("BENCH_CONCURRENCY", defaults[2]))
    num_requests = int(os.environ.get("BENCH_REQUESTS", defaults[3]))
    if eng_cfg is None:
        # baseline-profile engine shape follows the (possibly overridden)
        # load shape: chunked prefill at half the ISL bucket, one decode
        # bucket at the concurrency. 70B does not fit one chip — BENCH_MESH
        # supplies the (dp, tp) axes over the slice.
        def _pow2(n):
            b = 1
            while b < n:
                b *= 2
            return b

        chunk = max(256, _pow2(isl) // 2)
        seq_len = isl + osl + 64
        blocks_needed = concurrency * (seq_len // 16 + 2) * 2
        eng_cfg = EngineConfig(
            num_blocks=int(os.environ.get("BENCH_NUM_BLOCKS",
                                          max(8192, blocks_needed))),
            max_model_len=seq_len,
            # budget > chunk bucket: decode seats coexist with a full
            # chunk instead of fragmenting every prompt
            max_num_batched_tokens=chunk + _pow2(concurrency),
            prefill_buckets=(chunk,),
            decode_buckets=(_pow2(concurrency),),
            max_num_seqs=concurrency,
            mesh_shape=tuple(int(x) for x in os.environ.get(
                "BENCH_MESH", "1,1").split(",")),
            decode_steps=decode_steps, pipeline_depth=pipe_depth,
            block_lookahead=lookahead, **spec_kw,
        )

    engine = InferenceEngine(model_cfg, eng_cfg)
    await engine.start()

    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(engine.params)
    )

    rng = random.Random(0)
    vocab = model_cfg.vocab_size

    def make_prompt() -> list:
        return [rng.randrange(1, vocab) for _ in range(isl)]

    ttfts: list = []
    itls: list = []
    done_tokens = [0]

    async def one_request(i: int) -> None:
        req = Request(
            request_id=f"bench-{i}", token_ids=make_prompt(),
            max_tokens=osl, temperature=0.0, ignore_eos=True,
        )
        t0 = time.monotonic()
        ts: list = []  # per-token client arrival timestamps
        async for out in engine.submit(req):
            now = time.monotonic()
            if out.index == 0:
                ttfts.append(now - t0)
            ts.append(now)
            done_tokens[0] += 1
        itls.extend(_itl_samples(ts))

    # warmup: trigger every XLA compile (prefill + full decode bucket)
    import asyncio

    await asyncio.gather(*(one_request(-1 - i) for i in range(concurrency)))
    ttfts.clear()
    itls.clear()
    done_tokens[0] = 0
    engine.num_fetch_syncs = 0  # count only measured-loop host syncs
    # flight recorder: drop warmup windows from the live gauges and arm
    # the steady-state recompile watchdog — any compile from here on is a
    # shape leak the result will carry in recompiles_steady_state
    if hasattr(engine, "mark_obs_warmup_done"):
        engine.mark_obs_warmup_done()

    sem = asyncio.Semaphore(concurrency)

    async def gated(i: int) -> None:
        async with sem:
            await one_request(i)

    t_start = time.monotonic()
    await asyncio.gather(*(gated(i) for i in range(num_requests)))
    elapsed = time.monotonic() - t_start
    await engine.stop()

    # per-CHIP normalisation: the engine may run tp/dp over several chips
    # (BENCH_MESH); aggregate throughput divided by the mesh size keeps
    # the unit honest and MFU <= 1
    n_chips = eng_cfg.mesh_shape[0] * eng_cfg.mesh_shape[1]
    out_toks = done_tokens[0] / elapsed / n_chips
    # MFU from the shared analytic model (dynamo_tpu.observability.flops):
    # matmul term = 2 * active params / token, PLUS the attention-score
    # term (4 * L * H * hd * context / token) the old 2·N·params formula
    # dropped. Both are reported: "mfu" is the total, "mfu_model_only"
    # the matmul-only figure comparable to older BENCH_*.json files.
    # n_params spans the whole mesh, so FLOPs are divided by n_chips.
    from dynamo_tpu.engine import quant
    from dynamo_tpu.observability.flops import FlopsModel

    fm = FlopsModel(model_cfg)
    processed = num_requests * (isl + osl) / elapsed
    # quantized weights run the matmuls on the 8-bit MXU path — MFU must
    # be measured against the int8/fp8 roofline, not the bf16 one
    peak = _peak_flops(getattr(dev, "device_kind", ""), platform,
                       weight_dtype if quant.is_quantized(weight_dtype)
                       else model_cfg.dtype)
    # paged-cache bytes per token across all layers: K + V pages at the
    # storage width, plus one float32 scale per (token, kv_head) when the
    # cache is quantized
    _kv_elems = (2 * model_cfg.num_layers * model_cfg.num_kv_heads
                 * model_cfg.head_dim_)
    kv_bytes_per_token = _kv_elems * quant.kv_bytes_per_elem(
        kv_dtype, model_cfg.dtype)
    if quant.is_quantized(kv_dtype):
        kv_bytes_per_token += 2 * model_cfg.num_layers \
            * model_cfg.num_kv_heads * 4
    # no published peak off-TPU (run_bench is imported by CPU tests): the
    # MFU fields are then None, not a number against a nominal peak
    mfu = mfu_model_only = None
    if peak:
        mfu = round(num_requests * fm.sequence_flops(isl, osl)
                    / elapsed / n_chips / peak, 4)
        mfu_model_only = round(
            fm.matmul_per_token * processed / n_chips / peak, 4)
    # the LIVE recorder's post-warmup view (padding and spec-reject waste,
    # per-class MFU, steady-state recompiles) — measured at dispatch/landing
    # inside the engine, not recomputed from request counts
    obs = (engine.obs_snapshot()
           if hasattr(engine, "obs_snapshot") else {}) or {}
    result = {
        "metric": f"output tok/s/chip, llama-{model_name} agg greedy "
                  f"ISL={isl} OSL={osl} conc={concurrency} "
                  f"chips={n_chips} ({platform})",
        "value": round(out_toks, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(
            out_toks / GPU_PARITY_TOKS.get(model_name, 10_000.0), 4
        ),
        "ttft_p50_ms": round(_pct(ttfts, 50) * 1e3, 1),
        "ttft_p99_ms": round(_pct(ttfts, 99) * 1e3, 1),
        "itl_p50_ms": round(_pct(itls, 50) * 1e3, 2),
        "itl_p99_ms": round(_pct(itls, 99) * 1e3, 2),
        "itl_mean_ms": round(
            sum(itls) / len(itls) * 1e3 if itls else 0.0, 2),
        "prefill_chunk_tokens": prefill_chunk,
        "weight_dtype": weight_dtype,
        "kv_dtype": kv_dtype,
        "kv_bytes_per_token": round(kv_bytes_per_token, 1),
        "requests": num_requests,
        "elapsed_s": round(elapsed, 2),
        "platform": platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "backend_init_s": round(backend_init_s, 1),
        "n_params": n_params,
        "processed_tok_s": round(processed, 1),
        "mfu": mfu,
        "mfu_model_only": mfu_model_only,
        # live flight-recorder accounting (engine-measured, post-warmup)
        "mfu_prefill": (round(obs["mfu_prefill"], 6)
                        if "mfu_prefill" in obs else None),
        "mfu_decode": (round(obs["mfu_decode"], 6)
                       if "mfu_decode" in obs else None),
        "padding_waste_ratio": round(obs.get("padding_waste_ratio", 0.0), 4),
        "goodput_tok_s": round(obs.get("goodput_tok_s", 0.0), 1),
        "recompiles_steady_state": int(
            obs.get("recompiles_steady_state", 0)),
        # channel-traffic counters: each delta is 2 uploads, each prefill
        # 2, cols 1, windows 0 — the serial-channel budget explains the
        # gap between device compute (~3 ms/window) and wall time
        "num_windows": getattr(engine, "num_windows", 0),
        "num_deltas": getattr(engine, "num_deltas", 0),
        "num_delta_rows": getattr(engine, "num_delta_rows", 0),
        "num_cols_uploads": getattr(engine, "num_cols_uploads", 0),
        "num_prefills": getattr(engine, "num_prefill_dispatches", 0),
        # host-sync efficiency: output tokens landed per device->host
        # result fetch; speculative decoding's point, where a host sync is
        # dear, is pushing this above 1.0
        "num_fetch_syncs": getattr(engine, "num_fetch_syncs", 0),
        "tokens_per_host_sync": round(
            done_tokens[0] / max(1, getattr(engine, "num_fetch_syncs", 0)),
            3),
        "spec_mode": spec_mode,
        "spec_acceptance_rate": round(
            engine.spec_stats.acceptance_rate
            if getattr(engine, "spec_stats", None) is not None else 0.0,
            4),
    }
    if getattr(engine, "attention_impl_choice", None) is not None:
        choice = engine.attention_impl_choice
        result["attention_impl_choice"] = choice
        # the tuned kernel tiles that actually served this run ([0, 0] =
        # kernel defaults) and whether they came from the persisted
        # autotune cache (DYNTPU_AUTOTUNE_CACHE) or a fresh sweep
        tiles = choice.get("tiles") or {}
        for cls in ("decode", "spec", "prefill"):
            result[f"attention_tile_config_{cls}"] = tiles.get(cls, [0, 0])
        result["autotune_cache_hit"] = bool(
            choice.get("autotune_cache_hit", False))
    # adaptive bucket ladder state (flat static grid when the ladder is
    # off: rungs_n == len(configured buckets), splits/retires == 0)
    for kind in ("decode", "prefill"):
        n_rungs = obs.get(f"ladder_{kind}_rungs_n")
        if n_rungs is None:
            continue
        result[f"ladder_{kind}_rungs_n"] = int(n_rungs)
        result[f"ladder_{kind}_splits"] = int(
            obs.get(f"ladder_{kind}_splits_total", 0))
        result[f"ladder_{kind}_retires"] = int(
            obs.get(f"ladder_{kind}_retires_total", 0))
        result[f"ladder_{kind}_budget_remaining"] = int(
            obs.get(f"ladder_{kind}_budget_remaining", 0))
    if on_tpu:
        try:
            tuned = (getattr(engine, "attention_impl_choice", None)
                     or {}).get("tiles") or {}
            result.update(_kernel_check(spec_k, tiles=tuned))
        except Exception as e:  # the headline number still stands
            result["kernel_error"] = f"{type(e).__name__}: {e}"
        result["notes"] = (
            "next-run on-TPU targets for the autotune+ladder campaign: "
            "MFU >= 0.15, >= 3x tok/s/chip over the 455 r05 baseline, "
            "kernel_speedup_decode/spec/prefill >= 1.3 with swept "
            "attention_tile_config_* (run with DYNTPU_AUTOTUNE_CACHE set "
            "to persist winners; DYNTPU_LADDER_ENABLED=1 for adaptive "
            "buckets); quantized-serving target (BENCH_WEIGHT_DTYPE="
            "int8 BENCH_KV_DTYPE=int8): >= 1.5x decode tok/s/chip over "
            "the 455 bf16 baseline from halved weight/KV traffic and "
            "the doubled 8-bit MXU roofline")
    faulthandler.cancel_dump_traceback_later()
    return result


# --------------------- parent-side orchestration --------------------------


def _stderr_tail(path: str, limit: int = 1800) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - 8192))
            text = f.read().decode("utf-8", "replace")
    except OSError:
        return ""
    # drop blank lines, keep the informative tail
    lines = [ln for ln in text.splitlines() if ln.strip()]
    tail = " | ".join(lines[-12:])
    return tail[-limit:]


def _run_attempt(env: dict, probe_timeout: float, bench_timeout: float):
    """One child run (probe handshake + measured loop).

    Returns (result|None, probed_platform|None, err|None). ``err`` carries
    the failure stage, timings, and the child's full stderr tail.
    """
    stderr_file = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".stderr", delete=False
    )
    try:
        return _run_attempt_inner(env, probe_timeout, bench_timeout,
                                  stderr_file)
    finally:
        try:
            stderr_file.close()
            os.unlink(stderr_file.name)
        except OSError:
            pass


def _run_attempt_inner(env, probe_timeout, bench_timeout, stderr_file):
    proc = subprocess.Popen(
        [sys.executable, __file__, "--child"],
        stdout=subprocess.PIPE, stderr=stderr_file, text=True, env=env,
    )
    lines: list = []
    lines_lock = threading.Condition()

    def reader():
        for line in proc.stdout:
            with lines_lock:
                lines.append(line.strip())
                lines_lock.notify_all()
        with lines_lock:
            lines_lock.notify_all()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    t0 = time.monotonic()

    def wait_for(pred, deadline):
        while True:
            with lines_lock:
                for ln in lines:
                    if pred(ln):
                        return ln
                if proc.poll() is not None and not t.is_alive():
                    return None
                remain = deadline - time.monotonic()
                if remain <= 0:
                    return None
                lines_lock.wait(min(remain, 5.0))

    def fail(stage):
        proc.kill()
        proc.wait()
        stderr_file.flush()
        elapsed = time.monotonic() - t0
        tail = _stderr_tail(stderr_file.name)
        rc = proc.returncode
        return (
            f"{stage} after {elapsed:.0f}s (rc={rc}, "
            f"JAX_PLATFORMS={env.get('JAX_PLATFORMS')!r}); stderr: "
            f"{tail or '<empty>'}"
        )

    probe_line = wait_for(
        lambda ln: ln.startswith("PROBE|"), t0 + probe_timeout
    )
    if probe_line is None:
        stage = ("backend init timed out" if proc.poll() is None
                 else "child died during backend init")
        return None, None, fail(stage)
    platform = probe_line.split("|", 2)[1]
    if platform != "tpu":
        # a benchmark number comes from a chip or not at all
        return None, platform, fail(f"no chip: child came up on {platform!r}")

    json_line = wait_for(
        lambda ln: ln.startswith("{"), t0 + probe_timeout + bench_timeout
    )
    if json_line is None:
        stage = ("bench timed out" if proc.poll() is None
                 else "child died mid-bench")
        return None, platform, fail(stage)
    try:
        # the JSON is already in hand — don't let a hang in device
        # teardown stall the parent
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    try:
        return json.loads(json_line), platform, None
    except json.JSONDecodeError as e:
        return None, platform, f"bad bench JSON: {e}"


async def run_planner_sim() -> dict:
    """SLO columns for the bench trajectory: one compact simulated-cluster
    run (CPU-only, seconds) through the live planner/orchestrator loop —
    request-level slo_violation_rate plus per-tier TTFT/ITL percentiles."""
    import logging

    logging.getLogger("dynamo_tpu").setLevel(logging.WARNING)
    from dynamo_tpu.mocker.cluster import SimScenario, run_scenario

    seed = int(os.environ.get("BENCH_PLANNER_SEED", 0))
    with tempfile.TemporaryDirectory() as workdir:
        rep = await run_scenario(SimScenario(seed=seed), workdir)
    rate = rep["slo_violation_rate"]
    fields = {
        "slo_violation_rate": (round(rate, 4) if rate is not None else None),
        "sim_recovery_windows": rep["recovery_windows"],
        "sim_requests": rep["num_requests"],
        "sim_shed": rep["num_shed_total"],
        "sim_degradation_max_level": rep["degradation_max_level"],
        "sim_seed": seed,
    }
    for tier, summary in sorted(rep["tiers"].items()):
        for key in ("ttft_p50_s", "ttft_p99_s", "itl_p50_s", "itl_p99_s"):
            value = summary.get(key)
            fields[f"tier{tier}_{key[:-2]}_ms"] = (
                round(value * 1000.0, 2) if value is not None else None)
    return fields


def _planner_sim_fields(base_env: dict, timeout_s: float = 180.0) -> dict:
    """Run the sim in a CPU-pinned subprocess so a TPU bench run never loads
    extra state into this process; any failure degrades to an error note,
    never a broken bench. BENCH_PLANNER_SIM=0 skips it entirely."""
    if os.environ.get("BENCH_PLANNER_SIM", "1").lower() in ("0", "false",
                                                            "off"):
        return {}
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--planner-sim"],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
        line = next(ln for ln in reversed(out.stdout.splitlines())
                    if ln.startswith("{"))
        return json.loads(line)
    except Exception as e:  # noqa: BLE001 — must never break the bench
        return {"planner_sim_error": f"{type(e).__name__}: {e}"[:200]}


async def run_replay_gate() -> dict:
    """Trace-replay scoreboard columns: one seeded bursty multi-tenant
    replay (CPU-only) against a real-engine SimCluster — per-tier latency,
    SLO-violation rate, prefix-hit rate, and whether the recorder/span
    cross-checks agreed with the client-side measurements."""
    import logging

    logging.getLogger("dynamo_tpu").setLevel(logging.WARNING)
    from dynamo_tpu.replay.__main__ import scenario_config
    from dynamo_tpu.replay.driver import ReplaySettings, run_cluster_replay
    from dynamo_tpu.replay.scoreboard import build_scoreboard
    from dynamo_tpu.replay.trace import (
        generate_gauntlet_trace, generate_trace,
    )

    seed = int(os.environ.get("BENCH_REPLAY_SEED", 0))
    trace = generate_trace(scenario_config("bursty", seed))
    with tempfile.TemporaryDirectory() as workdir:
        run = await run_cluster_replay(
            trace, ReplaySettings(time_scale=2.0), workdir=workdir)
    rep = build_scoreboard(trace, run)
    fields = {
        "replay_ok": rep["ok"],
        "replay_seed": seed,
        "replay_digest": rep["outcome_digest"],
        "replay_requests": rep["requests"],
        "replay_aborted": rep["aborted"],
        "replay_errors": rep["errors"],
        "replay_slo_violation_rate": rep["slo_violation_rate"],
        "replay_prefix_hit_rate": rep["prefix_hit_rate"],
        "replay_chip_s_per_1m_tok": rep["chip_seconds_per_1m_output_tokens"],
    }
    for tier, row in sorted(rep["tiers"].items()):
        for key in ("ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms", "itl_p99_ms"):
            fields[f"replay_tier{tier}_{key}"] = row[key]

    # chaos gauntlet alongside the clean replay: seeded fault waves with
    # attributed-recovery scoring; token loss must be exactly zero
    chaos_trace = generate_gauntlet_trace(seed)
    with tempfile.TemporaryDirectory() as workdir:
        chaos_run = await run_cluster_replay(
            chaos_trace,
            ReplaySettings(time_scale=2.0, stall_timeout_s=0.5,
                           stall_timeout_per_token_s=0.01),
            workdir=workdir)
    chaos = build_scoreboard(chaos_trace, chaos_run)
    fields.update({
        "chaos_ok": chaos["ok"],
        "chaos_checks_failed": sorted(
            k for k, v in chaos["checks"].items() if not v.get("ok")),
        "chaos_failed_reasons": {
            k: v.get("reason", "") for k, v in chaos["checks"].items()
            if not v.get("ok")},
        "chaos_digest": chaos["outcome_digest"],
        "chaos_faults_fired": sum(chaos["faults_fired"].values()),
        "chaos_slo_violation_rate": chaos["chaos_slo_violation_rate"],
        "chaos_recovery_windows_p99": chaos["chaos_recovery_windows_p99"],
        "chaos_token_loss": chaos["chaos_token_loss"],
    })
    return fields


def _replay_fields(base_env: dict, timeout_s: float = 420.0) -> dict:
    """Replay gate in a CPU-pinned subprocess, same contract as
    ``_planner_sim_fields``: failures degrade to an error note, never a
    broken bench. BENCH_REPLAY=0 skips it entirely."""
    if os.environ.get("BENCH_REPLAY", "1").lower() in ("0", "false", "off"):
        return {}
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--replay"],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
        line = next(ln for ln in reversed(out.stdout.splitlines())
                    if ln.startswith("{"))
        return json.loads(line)
    except Exception as e:  # noqa: BLE001 — must never break the bench
        return {"replay_error": f"{type(e).__name__}: {e}"[:200]}


async def run_prefix_bench() -> dict:
    """Global-prefix-cache columns: one seeded shared-prefix dataset served
    twice by a tiny CPU engine — prefix caching on vs off — reporting the
    measured hit rate, the analytic prefill-FLOPs saved ratio, and TTFT
    p50/p99 for both modes. Greedy outputs must match byte-for-byte across
    the two runs, and the radix prefix index's own hit accounting must agree
    with the scheduler's (the same invariant the replay ``prefix_vs_index``
    cross-check enforces)."""
    import logging

    logging.getLogger("dynamo_tpu").setLevel(logging.WARNING)
    from benchmarks.datagen import (
        PrefixDatasetConfig, generate_prefix_dataset, prefix_ground_truth,
    )
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.engine import InferenceEngine, Request
    from dynamo_tpu.observability.flops import FlopsModel

    seed = int(os.environ.get("BENCH_PREFIX_SEED", 0))
    print(f"PREFIX_SEED={seed}", flush=True)
    isl = int(os.environ.get("BENCH_PREFIX_ISL", 512))
    osl = int(os.environ.get("BENCH_PREFIX_OSL", 8))
    block_size = 16
    # high prefix_ratio with few groups: the regime the global prefix cache
    # targets (system prompts / few-shot templates shared across requests)
    ds = generate_prefix_dataset(PrefixDatasetConfig(
        num_requests=int(os.environ.get("BENCH_PREFIX_REQUESTS", 24)),
        isl=isl, prefix_ratio=0.94, groups=2, branches=2,
        vocab_size=200, vocab_offset=10, seed=seed,
    ))
    gt = prefix_ground_truth(ds)
    model_cfg = ModelConfig.tiny(vocab_size=256)
    fm = FlopsModel(model_cfg)

    def make_engine(cache_on: bool) -> InferenceEngine:
        return InferenceEngine(
            model_cfg,
            EngineConfig(
                num_blocks=512, block_size=block_size,
                max_model_len=2 * isl, max_num_batched_tokens=isl,
                prefill_buckets=(32, 64, 128, 256, isl),
                decode_buckets=(4,), max_num_seqs=4,
                enable_prefix_caching=cache_on,
                # XLA path on CPU: pallas-interpret is a correctness tool
                # with a flat ~300 ms/step cost that would swamp the
                # prefill-size signal this scenario measures
                attention_impl="einsum",
            ),
            seed=0,
        )

    async def run_mode(cache_on: bool) -> dict:
        eng = make_engine(cache_on)
        if cache_on:
            eng.attach_prefix_cache(worker_id=0)
        sched = eng.scheduler

        async def one(i: int, r) -> tuple:
            h0 = sched.stats.prefix_cache_hits
            t0 = time.perf_counter()
            ttft, toks = None, []
            req = Request(request_id=f"px-{i}", token_ids=list(r.token_ids),
                          max_tokens=osl, temperature=0.0, ignore_eos=True)
            async for out in eng.submit(req):
                if ttft is None:
                    ttft = time.perf_counter() - t0
                toks.append(out.token_id)
            cached = (sched.stats.prefix_cache_hits - h0) * block_size
            return ttft, toks, cached

        # warm the XLA compile caches over the full dataset (every
        # cached-remainder prefill bucket the timed pass will hit) then
        # clear so the timed pass starts from an empty pool
        for i, r in enumerate(ds):
            await one(i, r)
        eng.clear_kv_blocks()

        hits0 = sched.stats.prefix_cache_hits
        queries0 = sched.stats.prefix_cache_queries
        idx0 = (float(eng.prefix.index.hit_tokens_total)
                if cache_on and eng.prefix is not None else 0.0)
        ttfts, outputs = [], []
        full_flops = computed_flops = 0.0
        for i, r in enumerate(ds):
            ttft, toks, cached = await one(i, r)
            ttfts.append(ttft if ttft is not None else 0.0)
            outputs.append(toks)
            cached = min(cached, isl)
            full_flops += fm.step_flops(isl, fm.sequence_context_sum(isl))
            computed_flops += fm.step_flops(
                isl - cached, fm.sequence_context_sum(isl - cached,
                                                      start=cached))
        hits = sched.stats.prefix_cache_hits - hits0
        queries = sched.stats.prefix_cache_queries - queries0
        index_tokens = None
        if cache_on and eng.prefix is not None:
            index_tokens = float(eng.prefix.index.hit_tokens_total) - idx0
        await eng.stop()
        return {
            "ttft_p50_ms": round(_pct(ttfts, 50) * 1e3, 2),
            "ttft_p99_ms": round(_pct(ttfts, 99) * 1e3, 2),
            "hit_rate": (hits / queries if queries else 0.0),
            "hit_tokens": hits * block_size,
            "flops_saved_ratio": 1.0 - computed_flops / max(full_flops, 1e-9),
            "index_hit_tokens": index_tokens,
            "outputs": outputs,
        }

    on = await run_mode(True)
    off = await run_mode(False)
    speedup = off["ttft_p50_ms"] / max(on["ttft_p50_ms"], 1e-9)
    return {
        "prefix_seed": seed,
        "prefix_hit_rate": round(on["hit_rate"], 4),
        "prefill_flops_saved_ratio": round(on["flops_saved_ratio"], 4),
        "prefix_ttft_p50_ms_cache_on": on["ttft_p50_ms"],
        "prefix_ttft_p99_ms_cache_on": on["ttft_p99_ms"],
        "prefix_ttft_p50_ms_cache_off": off["ttft_p50_ms"],
        "prefix_ttft_p99_ms_cache_off": off["ttft_p99_ms"],
        "prefix_ttft_speedup_p50": round(speedup, 2),
        # byte-identical greedy outputs cache-on vs cache-off: the
        # correctness bar — a hit must never change what gets generated
        "prefix_outputs_match": on["outputs"] == off["outputs"],
        # radix index hit accounting vs the scheduler's measured hits
        # (same invariant as the replay prefix_vs_index cross-check)
        "prefix_index_agree": (
            on["index_hit_tokens"] == float(on["hit_tokens"])),
        "prefix_hit_potential_tokens": gt["prefix_hit_potential_tokens"],
        "prefix_total_prompt_tokens": gt["total_prompt_tokens"],
    }


def _prefix_fields(base_env: dict, timeout_s: float = 300.0) -> dict:
    """Shared-prefix scenario in a CPU-pinned subprocess, same contract as
    ``_planner_sim_fields``: failures degrade to an error note, never a
    broken bench. BENCH_PREFIX=0 skips it entirely."""
    if os.environ.get("BENCH_PREFIX", "1").lower() in ("0", "false", "off"):
        return {}
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--prefix-bench"],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
        line = next(ln for ln in reversed(out.stdout.splitlines())
                    if ln.startswith("{"))
        return json.loads(line)
    except Exception as e:  # noqa: BLE001 — must never break the bench
        return {"prefix_bench_error": f"{type(e).__name__}: {e}"[:200]}


def main() -> None:
    probe_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT", 600))
    bench_timeout = float(os.environ.get("BENCH_TIMEOUT", 2400))
    retries = int(os.environ.get("BENCH_PROBE_RETRIES", 2))
    errors = []
    result = None

    base_env = dict(os.environ)
    for attempt in range(1, retries + 1):
        result, platform, err = _run_attempt(
            base_env, probe_timeout, bench_timeout
        )
        if result is not None:
            break
        errors.append(f"attempt {attempt}/{retries}: {err}")
        if platform is not None and platform != "tpu":
            break  # no chip here; another attempt finds none either
    if result is None:
        print("bench failed — no result:\n" + "\n".join(errors),
              file=sys.stderr)
        sys.exit(1)
    result.update(_planner_sim_fields(base_env))
    result.update(_replay_fields(base_env))
    result.update(_prefix_fields(base_env))
    print(json.dumps(result))


if __name__ == "__main__":
    if "--child" in sys.argv:
        import asyncio

        print(json.dumps(asyncio.run(run_bench())))
    elif "--planner-sim" in sys.argv:
        import asyncio

        print(json.dumps(asyncio.run(run_planner_sim())))
    elif "--replay" in sys.argv:
        import asyncio

        print(json.dumps(asyncio.run(run_replay_gate())))
    elif "--prefix-bench" in sys.argv:
        import asyncio

        print(json.dumps(asyncio.run(run_prefix_bench())))
    else:
        main()
