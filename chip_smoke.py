#!/usr/bin/env python3
"""chip_smoke.py — does the serving path still start on the chip?

    python chip_smoke.py               # one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips  # one four-chip host, that phase only
    python chip_smoke.py --rehearse    # CPU, tiny sizes: control flow only,
                                       # can never print ok (exit code 3)

The parent never imports JAX: a chip belongs to one process at a time, so the
parent starts children one after another and each is the sole owner of the
chip while it lives.

  device child   kernel phase: the ragged paged-attention kernel compiled for
                 real (``tpu_custom_call`` in the lowered text, not
                 interpreted) and run for every class the engine can select at
                 Llama-3.2-1B widths against ``reference_naive``;
                 transport phase: chained decode step / host enqueue / one
                 host<->device sync / one fresh upload, on this machine.
  serve phase    ``python -m dynamo_tpu.runtime.store`` + ``python -m
                 dynamo_tpu.worker --model 1b`` + ``python -m
                 dynamo_tpu.frontend`` over TCP, random weights from the seed,
                 a handful of streamed OpenAI requests over HTTP.  The device
                 in the final line is what the WORKER reports (its
                 system-server ``engine`` probe), not a JAX call of our own.

One JSON line per phase (observations from one run, not performance
records), then — only if every phase passed on a TPU — the last line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Anything else exits non-zero and prints no ``ok``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
EXIT_FAILED = 1
EXIT_NOT_TPU = 3          # --rehearse: phases passed, platform is not a TPU


class PhaseFailed(Exception):
    pass


def count_remats(text: str) -> int:
    """XLA's involuntary-remat warnings go from C++ straight to fd 2, so the
    worker's own counter can miss them: count them in its captured log."""
    from dynamo_tpu.observability.compilewatch import REMAT_RE  # no JAX

    return len(REMAT_RE.findall(text))


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ===========================================================================
# children (these import JAX; each is the only process on the chip)
# ===========================================================================


def _child_setup():
    sys.path.insert(0, HERE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from dynamo_tpu.utils.device_env import configure_compile_cache

    configure_compile_cache()
    import jax

    d = jax.devices()
    return jax, {"platform": d[0].platform, "kind": d[0].device_kind,
                 "count": len(d)}


def _require_tpu(device: dict, rehearse: bool) -> None:
    if device["platform"] != "tpu" and not rehearse:
        raise PhaseFailed(f"JAX found no TPU (platform {device['platform']!r})")


# ------------------------------ kernel phase --------------------------------


def kernel_phase(jax, device: dict, rehearse: bool, seed: int) -> dict:
    """Every attention class the engine can select, compiled and run."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import attention_parity as parity
    from dynamo_tpu.engine import quant
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.ops.paged_attention import paged_attention_ragged

    on_tpu = device["platform"] == "tpu"
    if rehearse:
        mcfg = ModelConfig.tiny()
        base = EngineConfig(num_blocks=64, max_model_len=128, max_num_seqs=8,
                            decode_buckets=(8,), prefill_buckets=(16, 32),
                            spec_mode="ngram", spec_k=4)
        # (name, kv_dtype, B, T, W)
        classes = [("decode", "bf16", 8, 1, 4), ("prefill", "bf16", 2, 32, 4),
                   ("spec", "bf16", 4, 5, 4), ("decode_int8", "int8", 8, 1, 4),
                   ("prefill_int8", "int8", 2, 32, 4)]
    else:
        mcfg = ModelConfig.llama3_1b()
        base = EngineConfig(spec_mode="ngram", spec_k=4)   # defaults: bs 16
        W = base.max_blocks_per_seq                        # 512 → 8k context
        T = max(base.prefill_buckets)                      # 512 → q_tile 128
        classes = [
            ("decode_b8", "bf16", 8, 1, W),
            ("decode_b64", "bf16", 64, 1, 64),
            ("prefill", "bf16", 4, T, W),
            ("spec", "bf16", 8, base.spec_k + 1, 128),
            ("decode_int8", "int8", 8, 1, W),
            ("prefill_int8", "int8", 4, T, 128),
            ("decode_fp8", "fp8", 8, 1, 128),
            ("prefill_fp8", "fp8", 4, T, 128),
        ]
    out = {"phase": "kernel", "model": "tiny" if rehearse else "1b",
           "H": mcfg.num_heads, "KV": mcfg.num_kv_heads,
           "hd": mcfg.head_dim_, "block_size": base.block_size,
           "classes": {}}
    failed = []
    for name, kv_dtype, B, T, W in classes:
        eng = dataclasses.replace(base, kv_dtype=kv_dtype)
        attn_class = "decode" if T == 1 else ("spec" if T <= 5 else "prefill")
        case = parity.make_sweep_case(
            mcfg, eng, attn_class, B, T, W=W, seed=seed, poison=True)
        if mcfg.dtype != "bfloat16":  # tiny preset is f32
            tol = 2e-3
        else:
            tol = 5e-2 if quant.is_quantized(kv_dtype) else 2e-2
        q, kc, vc, tables, q_start, q_len, ctx_len = case["args"]
        ks, vs = case.get("k_scale"), case.get("v_scale")
        q_tile = 1 if T == 1 else 0
        fn = jax.jit(lambda *a, _T=T, _qt=q_tile, **k: paged_attention_ragged(
            *a, block_size=eng.block_size, max_q_len=_T, q_tile=_qt,
            interpret=not on_tpu, **k))
        args = [jnp.asarray(a) for a in (q, kc, vc, tables, q_start, q_len,
                                         ctx_len)]
        kw = {} if ks is None else {"k_scale": jnp.asarray(ks),
                                    "v_scale": jnp.asarray(vs)}
        t0 = time.monotonic()
        lowered = fn.lower(*args, **kw)
        is_custom_call = "tpu_custom_call" in lowered.as_text()
        got = np.asarray(lowered.compile()(*args, **kw)).astype(np.float64)
        secs = time.monotonic() - t0
        if ks is not None:  # anchor on the dequantized caches
            kc = quant.kv_dequantize_cache_np(kc, ks)
            vc = quant.kv_dequantize_cache_np(vc, vs)
        ref = parity.reference_naive(
            q, kc, vc, tables, q_start, q_len, ctx_len,
            block_size=eng.block_size)
        mask = parity.valid_slot_mask(q_start, q_len, got.shape[0])
        err = float(np.max(np.abs(got[mask] - ref[mask]), initial=0.0))
        finite = bool(np.isfinite(got).all())
        dead_zero = bool((got[~mask] == 0.0).all()) if T == 1 else None
        ok = (finite and err <= tol and dead_zero is not False
              and (is_custom_call or not on_tpu))
        out["classes"][name] = {
            "kv_dtype": kv_dtype, "B": B, "T": T, "W": W,
            "tpu_custom_call": is_custom_call, "interpret": not on_tpu,
            "max_abs_err": err, "tol": tol, "finite": finite,
            "dead_rows_zero": dead_zero,
            "lower_compile_run_s": round(secs, 2), "ok": ok,
        }
        if not ok:
            failed.append(name)
    out["ok"] = not failed
    if failed:
        out["failed"] = failed
    return out


# ----------------------------- transport phase ------------------------------


def transport_phase(jax, device: dict, rehearse: bool, seed: int) -> dict:
    """The four rows ROADMAP Queue 1 item 2 asks for, on this machine, with
    the serving step itself (``make_step_fn``, decode shape, 1B)."""
    import statistics

    import numpy as np

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig

    if rehearse:
        mcfg = ModelConfig.tiny()
        eng = EngineConfig(num_blocks=64, max_model_len=128, max_num_seqs=8,
                           decode_buckets=(8,), prefill_buckets=(16, 32))
        n_chain = 8
    else:
        mcfg = ModelConfig.llama3_1b()
        eng = EngineConfig()          # 2048 blocks, 8k, pallas decode
        n_chain = 64
    mesh = M.make_mesh((1, 1), jax.devices()[:1])
    params = M.init_params_sharded(jax.random.PRNGKey(seed), mcfg, mesh)
    cache = M.init_cache_sharded(mcfg, eng, mesh)
    step = M.make_step_fn(mcfg, eng, mesh)
    B, W = 8, eng.max_blocks_per_seq
    ctx = 4 * eng.block_size - 1 if rehearse else 1024
    nb = ctx // eng.block_size + 1
    tables = np.zeros((B, W), np.int32)
    for b in range(B):
        tables[b, :nb] = 1 + b * nb + np.arange(nb)
    host = (
        np.ones((B, 1), np.int32), np.full((B, 1), ctx, np.int32), tables,
        np.zeros((B,), np.int32), np.asarray(jax.random.PRNGKey(1)),
        np.zeros((B,), np.float32), np.zeros((B,), np.int32),
        np.ones((B,), np.float32), np.full((B,), -1, np.int32),
    )
    dev = tuple(jax.device_put(a) for a in host)
    t0 = time.monotonic()
    cache, sampled = step(params, cache, *dev)
    sampled.block_until_ready()
    compile_s = time.monotonic() - t0
    for _ in range(3):  # warm
        cache, sampled = step(params, cache, *dev)
    sampled.block_until_ready()

    # row 1+2: N chained steps, all args on device, one sync at the end
    enqueue = []
    t0 = time.monotonic()
    for _ in range(n_chain):
        t1 = time.monotonic()
        cache, sampled = step(params, cache, *dev)
        enqueue.append(time.monotonic() - t1)
    t_enq = time.monotonic() - t0
    sampled.block_until_ready()
    chained_ms = (time.monotonic() - t0) / n_chain * 1e3

    # row 3: one sync — a step whose result the host waits for, each time.
    # The call's own return time here is the enqueue cost on an EMPTY queue
    # (in the chained loop above the runtime throttles the host once enough
    # steps are in flight, so that loop's per-call time is mostly waiting).
    synced, idle_enqueue = [], []
    for _ in range(16):
        t1 = time.monotonic()
        cache, sampled = step(params, cache, *dev)
        idle_enqueue.append((time.monotonic() - t1) * 1e3)
        np.asarray(sampled)
        synced.append((time.monotonic() - t1) * 1e3)
    # ... and the bare device->host read of a result that is already there
    reads = []
    for _ in range(16):
        cache, sampled = step(params, cache, *dev)
        sampled.block_until_ready()
        t1 = time.monotonic()
        np.asarray(sampled)
        reads.append((time.monotonic() - t1) * 1e3)

    # row 4: one fresh upload (a [B] int32 row and a [B, W] block table)
    up_small, up_table = [], []
    for i in range(16):
        a = np.full((B,), i, np.int32)
        t1 = time.monotonic()
        jax.device_put(a).block_until_ready()
        up_small.append((time.monotonic() - t1) * 1e3)
        tb = tables + (i % 2)
        t1 = time.monotonic()
        jax.device_put(tb).block_until_ready()
        up_table.append((time.monotonic() - t1) * 1e3)

    med = statistics.median
    ms = jax.devices()[0].memory_stats() or {}
    return {
        "phase": "transport", "ok": True, "model": "tiny" if rehearse else "1b",
        "shape": {"B": B, "T": 1, "W": W, "ctx": ctx},
        "attention": {k: dict(v) for k, v in M.ATTENTION_TRACES.items()},
        "step_compile_s": round(compile_s, 2),
        "chained_decode_step_ms": chained_ms,
        "chained_enqueue_share": t_enq / (chained_ms * n_chain / 1e3),
        "host_enqueue_idle_queue_ms_median": med(idle_enqueue),
        "host_enqueue_in_chain_ms_median": med(enqueue) * 1e3,
        "host_enqueue_in_chain_ms_min": min(enqueue) * 1e3,
        "host_enqueue_in_chain_ms_max": max(enqueue) * 1e3,
        "step_plus_sync_ms_median": med(synced),
        "sync_over_chained_ms": med(synced) - chained_ms,
        "ready_result_read_ms_median": med(reads),
        "upload_row_ms_median": med(up_small),
        "upload_block_table_ms_median": med(up_table),
        "n_chain": n_chain,
        "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
    }


# ------------------------- four-chip compare child --------------------------


def compare_phase(jax, device: dict, rehearse: bool, seed: int) -> dict:
    """The 1b preset on (1,4) and on (1,1) in ONE process: same seed, same
    prompts, first-token logits compared; the (1,4) step must hold
    collectives and the kernel must run under shard_map."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.observability import compilewatch

    if len(jax.devices()) < 4:
        raise PhaseFailed(f"need 4 devices, have {len(jax.devices())}")
    if rehearse:
        mcfg = ModelConfig.tiny()
        kw = dict(num_blocks=64, max_model_len=128, max_num_seqs=8,
                  decode_buckets=(8,), prefill_buckets=(16, 32))
        T = 32
    else:
        mcfg = ModelConfig.llama3_1b()
        kw = dict(num_blocks=512)
        T = 64
    B = 2
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, min(mcfg.vocab_size, 50000), (B, T)).astype(
        np.int32)
    positions = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    logits = {}
    text14, kernel_in_decode = "", False
    with compilewatch.capture_stderr() as cap:
        for shape in ((1, 4), (1, 1)):
            eng = EngineConfig(mesh_shape=shape, **kw)
            n = shape[0] * shape[1]
            mesh = M.make_mesh(shape, jax.devices()[:n])
            params = M.init_params_sharded(
                jax.random.PRNGKey(seed), mcfg, mesh)
            cache = M.init_cache_sharded(mcfg, eng, mesh)
            W = eng.max_blocks_per_seq
            nb = T // eng.block_size + 1
            tables = np.zeros((B, W), np.int32)
            for b in range(B):
                tables[b, :nb] = 1 + b * nb + np.arange(nb)

            def run(params, cache, tok, pos, tb):
                cache, h = M.forward(mcfg, eng, params, cache, tok, pos, tb,
                                     mesh=mesh)
                return cache, M.logits_fn(mcfg, params, h[:, -1])

            fn = jax.jit(run, donate_argnums=(1,))
            pre = fn.lower(params, cache, tokens, positions,
                           tables).compile()
            cache, lg_prefill = pre(params, cache, tokens, positions, tables)
            nxt = np.asarray(jnp.argmax(lg_prefill, -1)).astype(np.int32)
            # one decode step: the Pallas kernel (under shard_map at tp=4)
            dargs = (nxt[:, None], np.full((B, 1), T, np.int32), tables)
            dec = fn.lower(params, cache, *dargs).compile()
            cache, lg_decode = dec(params, cache, *dargs)
            if shape == (1, 4):
                text14 = pre.as_text() + dec.as_text()
                kernel_in_decode = "tpu_custom_call" in dec.as_text()
            logits[shape] = (np.asarray(lg_prefill, np.float32),
                             np.asarray(lg_decode, np.float32),
                             dict(M.ATTENTION_TRACES.get("decode", {})))
            del params, cache
    remats = count_remats(cap.text())
    tol = 0.1
    res = {"phase": "compare_1b_tp4_vs_tp1", "T": T, "B": B,
           "involuntary_remats": remats, "rel_tol_of_max_logit": tol}
    ok = remats == 0
    for i, name in enumerate(("prefill", "decode")):
        a, b = logits[(1, 4)][i], logits[(1, 1)][i]
        scale = float(np.max(np.abs(b)))
        diff = float(np.max(np.abs(a - b)))
        res[f"{name}_logits_max_abs_diff"] = diff
        res[f"{name}_logits_max_abs"] = scale
        res[f"{name}_greedy_equal"] = bool(
            (a.argmax(-1) == b.argmax(-1)).all())  # printed, not asserted
        ok = ok and np.isfinite(a).all() and diff <= tol * scale
    colls = {c: text14.count(c) for c in
             ("all-reduce", "all-gather", "collective-permute",
              "reduce-scatter", "all-to-all")}
    res["collectives_in_tp4_step"] = colls
    res["tpu_custom_call_in_tp4_decode_step"] = kernel_in_decode
    res["decode_attention"] = {"tp4": logits[(1, 4)][2],
                               "tp1": logits[(1, 1)][2]}
    ok = ok and sum(colls.values()) > 0
    for tr in res["decode_attention"].values():
        ok = ok and tr.get("impl") == "pallas"
        if device["platform"] == "tpu":
            ok = ok and tr.get("interpret") is False and kernel_in_decode
    res["ok"] = bool(ok)
    return res


def child_main(which: str, rehearse: bool, seed: int) -> int:
    try:
        jax, device = _child_setup()
        emit({"phase": "child_start", "child": which, "device": device})
        _require_tpu(device, rehearse)
        phases = {"device": (kernel_phase, transport_phase),
                  "compare": (compare_phase,)}[which]
        for phase in phases:
            res = phase(jax, device, rehearse, seed)
            from dynamo_tpu.utils.device_env import compile_cache_stats

            res["compile_cache"] = compile_cache_stats()
            res["device"] = device
            emit(res)
            if not res.get("ok"):
                return EXIT_FAILED
        return 0
    except PhaseFailed as e:
        emit({"phase": which, "ok": False, "error": str(e)})
        return EXIT_FAILED


# ===========================================================================
# parent (never imports JAX)
# ===========================================================================


def run_child(which: str, args, timeout: float) -> list:
    """Run one child to its end; relay and return its JSON lines."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", which,
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    errlog = os.path.join(args.logdir, f"child_{which}.stderr.log")
    with open(errlog, "w") as ef:
        try:
            proc = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=ef,
                                  text=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{which} child timed out after {timeout}s")
    lines = []
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            print(ln, flush=True)
            lines.append(json.loads(ln))
    if proc.returncode != 0:
        tail = open(errlog).read()[-3000:]
        sys.stderr.write(f"--- {which} child stderr tail ---\n{tail}\n")
        raise PhaseFailed(f"{which} child exited {proc.returncode}")
    return lines


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Proc:
    """A started process with its log; always reaped by ``stop_all``."""

    def __init__(self, name: str, cmd: list, env: dict, logdir: str):
        self.name = name
        self.log_path = os.path.join(logdir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.t_start = time.monotonic()
        self.p = subprocess.Popen(cmd, env=env, stdout=self._log,
                                  stderr=subprocess.STDOUT, cwd=HERE)

    def log_text(self) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def check_alive(self) -> None:
        rc = self.p.poll()
        if rc is not None:
            raise PhaseFailed(
                f"{self.name} exited early (rc={rc}); log tail:\n"
                + self.log_text()[-3000:])

    def stop(self, grace: float = 10.0) -> None:
        if self.p.poll() is None:
            self.p.send_signal(signal.SIGTERM)
            try:
                self.p.wait(grace)
            except subprocess.TimeoutExpired:
                self.p.kill()
                self.p.wait()
        self._log.close()


def http_json(port: int, method: str, path: str, body=None, timeout=10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None if body is None else json.dumps(body)
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        raw = r.read()
        return r.status, (json.loads(raw) if raw else None)
    finally:
        conn.close()


def scrape_metrics(port: int, pattern: str) -> dict:
    """Samples of the worker's /metrics whose name matches ``pattern``."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode(errors="replace")
    finally:
        conn.close()
    out = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#") and re.search(pattern, ln):
            name, _, val = ln.rpartition(" ")
            out[name] = float(val)
    return out


def wait_until(what: str, fn, procs, timeout: float, every: float = 0.5):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        for p in procs:
            p.check_alive()
        try:
            got = fn()
            if got:
                return got
        except (OSError, ValueError, http.client.HTTPException) as e:
            last = e
        time.sleep(every)
    raise PhaseFailed(f"timed out after {timeout}s waiting for {what} "
                      f"(last error: {last})")


def holds_accelerator(pid: int) -> dict:
    """Did this process load the TPU runtime or open an accelerator?"""
    try:
        with open(f"/proc/{pid}/maps") as f:
            maps = f.read()
    except OSError:
        maps = ""
    fds = []
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                tgt = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if tgt.startswith(("/dev/accel", "/dev/vfio")):
                fds.append(tgt)
    except OSError:
        pass
    return {"libtpu_mapped": "libtpu" in maps,
            "jaxlib_mapped": "jaxlib" in maps or "xla_extension" in maps,
            "accel_fds": sorted(set(fds))}


def stream_request(port: int, kind: str, model: str, prompt: str,
                   max_tokens: int, tag: str, out: dict) -> None:
    """One streamed OpenAI request; records what the checks need."""
    rec = {"tag": tag, "kind": kind, "max_tokens": max_tokens,
           "prompt_chars": len(prompt), "events": 0, "content_events": 0,
           "done": False, "errors": [], "text": ""}
    out[tag] = rec
    body = {"model": model, "stream": True, "max_tokens": max_tokens,
            "temperature": 0.0}
    if kind == "chat":
        path = "/v1/chat/completions"
        body["messages"] = [{"role": "user", "content": prompt}]
    else:
        path = "/v1/completions"
        body["prompt"] = prompt
    t0 = time.monotonic()
    stamps = []
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        rec["status"] = r.status
        if r.status != 200:
            rec["errors"].append(r.read().decode(errors="replace")[:500])
            return
        while True:
            line = r.fp.readline()
            if not line:
                break
            line = line.decode(errors="replace").strip()
            if line.startswith("event:") and "error" in line:
                rec["errors"].append(line)
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                rec["done"] = True
                break
            rec["events"] += 1
            ev = json.loads(payload)
            if "error" in ev:
                rec["errors"].append(json.dumps(ev)[:500])
                continue
            ch = (ev.get("choices") or [{}])[0]
            piece = (ch.get("delta") or {}).get("content") \
                if kind == "chat" else ch.get("text")
            if piece:
                stamps.append(time.monotonic() - t0)
                rec["content_events"] += 1
                rec["text"] += piece
            if ev.get("usage"):
                rec["usage"] = ev["usage"]
            if ch.get("finish_reason"):
                rec["finish_reason"] = ch["finish_reason"]
        conn.close()
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["errors"].append(f"{type(e).__name__}: {e}")
    rec["total_s"] = time.monotonic() - t0
    if stamps:
        rec["ttft_s"] = stamps[0]
        rec["stream_span_s"] = stamps[-1] - stamps[0]
        gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
        if gaps:
            rec["gap_ms_median"] = gaps[len(gaps) // 2] * 1e3
            rec["gap_ms_max"] = gaps[-1] * 1e3


def check_request(rec: dict) -> list:
    bad = []
    if rec.get("errors"):
        bad.append(f"errors {rec['errors']}")
    if not rec.get("done"):
        bad.append("no [DONE]")
    got = (rec.get("usage") or {}).get("completion_tokens")
    if got != rec["max_tokens"]:
        bad.append(f"completion_tokens {got} != {rec['max_tokens']}")
    # incremental: many content events, spread over time — not one batch
    if rec["content_events"] < 8 or rec.get("stream_span_s", 0.0) <= 0.0:
        bad.append(f"not incremental ({rec['content_events']} content "
                   f"events over {rec.get('stream_span_s', 0.0):.4f}s)")
    return bad


def make_prompts(seed: int, long_bytes: int) -> dict:
    import random

    rnd = random.Random(seed)
    words = ["tensor", "block", "cache", "router", "prefill", "decode",
             "mesh", "shard", "kernel", "token", "stream", "lease", "page",
             "queue", "window", "radix", "chip", "store"]

    def text(n):
        s = []
        size = 0
        while size < n:
            w = rnd.choice(words)
            s.append(w)
            size += len(w) + 1
        return " ".join(s)[:n]

    return {"short_a": text(48), "short_b": text(90), "short_c": text(40),
            "long_a": text(long_bytes), "long_b": text(long_bytes + 64)}


# a tiny-preset worker for --rehearse (CPU, interpreted kernel: keep it small)
REHEARSE_WORKER_ARGS = ("--num-blocks", "256", "--max-model-len", "512",
                        "--max-batched-tokens", "256", "--max-num-seqs", "8")


def judge_requests(results: dict, res: dict) -> list:
    """Summaries into ``res["requests"]``; returns the hard-check failures."""
    failures = []
    for tag, rec in results.items():
        res["requests"].append(summarize(rec))
        failures += [f"{tag}: {b}" for b in check_request(rec)]
    return failures


def judge_control_plane(holders: dict) -> list:
    """The store and the frontend must never have touched JAX: they would
    take the chip from the worker."""
    return [f"{who} touched JAX / the accelerator: {h}"
            for who, h in holders.items() if who != "worker"
            and (h["libtpu_mapped"] or h["accel_fds"] or h["jaxlib_mapped"])]


class Deployment:
    """store + worker(s) + frontend as separate processes over TCP."""

    def __init__(self, args, tag: str):
        self.args = args
        self.tag = tag
        self.procs: list = []
        self.workers: list = []
        self.store_port = free_port()
        self.http_port = free_port()
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = HERE + os.pathsep + self.env.get(
            "PYTHONPATH", "")
        self.env["PYTHONUNBUFFERED"] = "1"

    def start(self, name: str, module: str, argv: list, extra_env=None):
        env = dict(self.env)
        env.update(extra_env or {})
        p = Proc(f"{self.tag}_{name}", [sys.executable, "-m", module] + argv,
                 env, self.args.logdir)
        self.procs.append(p)
        return p

    def start_store(self):
        self.store = self.start(
            "store", "dynamo_tpu.runtime.store",
            ["--host", "127.0.0.1", "--port", str(self.store_port)])

        def up():
            with socket.create_connection(("127.0.0.1", self.store_port), 1):
                return True
        wait_until("store", up, [self.store], 30)

    def start_worker(self, name: str, model: str, tok: str, extra: list,
                     extra_env=None):
        sys_port = free_port()
        env = {"DYNTPU_SYSTEM_ENABLED": "1",
               "DYNTPU_SYSTEM_PORT": str(sys_port)}
        env.update(extra_env or {})
        w = self.start(
            name, "dynamo_tpu.worker",
            ["--model", model, "--model-name", f"smoke-{model}",
             "--tokenizer", tok,
             "--store-addr", f"127.0.0.1:{self.store_port}"] + extra, env)
        w.sys_port = sys_port
        self.workers.append(w)
        return w

    def wait_worker(self, w, timeout: float) -> dict:
        def ready():
            st, body = http_json(w.sys_port, "GET", "/health")
            return (body or {}).get("probes", {}).get("engine")
        rep = wait_until(f"{w.name} ready", ready, self.procs, timeout, 1.0)
        w.ready_s = time.monotonic() - w.t_start
        return rep

    def start_frontend(self, model: str, router_mode: str = "round_robin"):
        self.frontend = self.start(
            "frontend", "dynamo_tpu.frontend",
            ["--host", "127.0.0.1", "--port", str(self.http_port),
             "--store-addr", f"127.0.0.1:{self.store_port}",
             "--router-mode", router_mode])

        def listed():
            st, body = http_json(self.http_port, "GET", "/v1/models")
            return any(m.get("id") == model for m in (body or {}).get(
                "data", []))
        wait_until("model listed by frontend", listed, self.procs, 60)

    def engine_probe(self, w) -> dict:
        st, body = http_json(w.sys_port, "GET", "/health")
        return body["probes"]["engine"]

    def drain_worker(self, w, timeout: float = 60.0) -> int:
        st, body = http_json(w.sys_port, "POST", "/drain")
        if st != 202:
            raise PhaseFailed(f"POST /drain → {st} {body}")
        try:
            return w.p.wait(timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{w.name} did not exit within {timeout}s of "
                              "POST /drain")

    def stop_all(self):
        for p in reversed(self.procs):
            p.stop()


def summarize(rec: dict) -> dict:
    keep = ("tag", "kind", "prompt_chars", "max_tokens", "status", "done",
            "events", "content_events", "usage", "finish_reason", "ttft_s",
            "total_s", "stream_span_s", "gap_ms_median", "gap_ms_max",
            "errors")
    return {k: rec[k] for k in keep if k in rec}


def run_wave(port: int, model: str, wave: list, results: dict) -> None:
    threads = [threading.Thread(target=stream_request,
                                args=(port, kind, model, prompt, n, tag,
                                      results))
               for tag, kind, prompt, n in wave]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def compile_totals(probe: dict) -> dict:
    c = probe["compile"]
    return {"compiles_total": c["compiles_total"],
            "compile_secs_total": round(
                sum(c["compile_secs_by_fn"].values()), 2),
            "compiles_by_fn": c["compiles_by_fn"]}


def serve_phase(args, tok_path: str) -> dict:
    """Section 1: the Quickstart deployment, 1B full width and depth."""
    model = "tiny" if args.rehearse else "1b"
    served = f"smoke-{model}"
    if args.rehearse:
        wargs = list(REHEARSE_WORKER_ARGS)
        long_bytes, n_out = 300, (24, 32)
    else:
        wargs = ["--num-blocks", "2048", "--max-model-len", "8192",
                 "--attention-impl", "pallas"]
        long_bytes, n_out = 2000, (64, 128)
    prompts = make_prompts(args.seed, long_bytes)
    dep = Deployment(args, "serve")
    res: dict = {"phase": "serve", "model": model, "requests": []}
    try:
        dep.start_store()
        w = dep.start_worker("worker", model, tok_path, wargs)
        rep0 = dep.wait_worker(w, args.worker_timeout)
        res["worker_ready_s"] = round(w.ready_s, 2)
        dep.start_frontend(served)
        results: dict = {}
        # wave 1: one cold request — every program it needs compiles now
        run_wave(dep.http_port, served,
                 [("cold_chat_short", "chat", prompts["short_a"], n_out[0])],
                 results)
        rep1 = dep.engine_probe(w)
        # wave 2: concurrent, short and ~2k-token prompts, both endpoints
        run_wave(dep.http_port, served, [
            ("chat_long", "chat", prompts["long_a"], n_out[0]),
            ("completion_long", "completion", prompts["long_b"], n_out[1]),
            ("completion_short", "completion", prompts["short_b"], n_out[1]),
            ("chat_short", "chat", prompts["short_c"], n_out[1]),
        ], results)
        rep2 = dep.engine_probe(w)
        # wave 3: the same traffic again — steady state: prefix-cache hit
        # on the repeated long prompt, and nothing may compile
        run_wave(dep.http_port, served, [
            ("chat_long_repeat", "chat", prompts["long_a"], n_out[0]),
            ("completion_short_repeat", "completion", prompts["short_b"],
             n_out[1]),
            ("chat_short_repeat", "chat", prompts["short_c"], n_out[1]),
        ], results)
        rep3 = dep.engine_probe(w)
        # wave 4: wave 3 once more — the same shapes, prefix hits included:
        # what compiles now is a steady-state recompile
        run_wave(dep.http_port, served, [
            ("chat_long_repeat2", "chat", prompts["long_a"], n_out[0]),
            ("completion_short_repeat2", "completion", prompts["short_b"],
             n_out[1]),
            ("chat_short_repeat2", "chat", prompts["short_c"], n_out[1]),
        ], results)
        rep4 = dep.engine_probe(w)
        res["worker_metrics"] = scrape_metrics(
            w.sys_port, r"prefix|remat|recompile|kv_hit|cache_hit")
        holders = {"store": holds_accelerator(dep.store.p.pid),
                   "frontend": holds_accelerator(dep.frontend.p.pid),
                   "worker": holds_accelerator(w.p.pid)}
        rc = dep.drain_worker(w)
        wlog = w.log_text()
    finally:
        dep.stop_all()

    failures = judge_requests(results, res)
    same = results["chat_long"]["text"] == results["chat_long_repeat"]["text"]
    res["repeat_same_text"] = same
    res["repeat_ttft_s"] = [results["chat_long"].get("ttft_s"),
                            results["chat_long_repeat"].get("ttft_s")]
    # printed, not asserted: random weights have thin greedy margins and the
    # repeat runs in another batch, partly from cached prefix blocks
    # the device, from the process that owns it
    res["device"] = {"platform": rep4["platform"], "kind": rep4["device_kind"],
                     "count": rep4["device_count"]}
    res["mesh_device_ids"] = rep4["mesh_device_ids"]
    res["attention_traced"] = rep4["attention"]
    res["attention_choice"] = rep4["attention_choice"]
    res["native"] = rep4["native"]
    res["compile_cache"] = rep4["compile_cache"]
    res["compile_at_ready"] = compile_totals(rep0)
    res["compile_after_cold_request"] = compile_totals(rep1)
    res["compile_after_wave2"] = compile_totals(rep2)
    res["compile_after_repeat_wave"] = compile_totals(rep3)
    res["compile_after_steady_wave"] = compile_totals(rep4)
    res["steady_state_recompiles"] = (
        rep4["compile"]["compiles_total"] - rep3["compile"]["compiles_total"])
    res["engine_involuntary_remats_total"] = max(
        rep4["compile"]["involuntary_remats_total"],
        count_remats(wlog))
    res["memory"] = rep4["memory"]
    res["accelerator_holders"] = holders
    res["worker_exit_code_after_drain"] = rc
    failures += judge_control_plane(holders)
    if rc != 0:
        failures.append(f"worker exited {rc} after drain")
    dec = rep4["attention"].get("decode", {})
    if dec.get("impl") != "pallas":
        failures.append(f"decode attention resolved to {dec!r}, asked pallas")
    if rep4["platform"] == "tpu":
        if dec.get("interpret") is not False:
            failures.append("decode kernel ran in interpret mode on a TPU")
        if not holders["worker"]["libtpu_mapped"]:
            failures.append("worker reports tpu but has no libtpu mapped")
    if res["engine_involuntary_remats_total"]:
        failures.append("involuntary remats on a one-chip worker")
    res["ok"] = not failures
    if failures:
        res["failures"] = failures
        sys.stderr.write("--- worker log tail ---\n" + wlog[-4000:] + "\n")
    return res


# ------------------------------ four chips ----------------------------------


def four_chip_serve(args, tok_path: str) -> dict:
    """8b at --mesh 1,4 through the same three processes."""
    model = "tiny" if args.rehearse else "8b"
    served = f"smoke-{model}"
    if args.rehearse:
        wargs = list(REHEARSE_WORKER_ARGS)
        n_out = 16
    else:
        wargs = ["--num-blocks", "1024", "--max-model-len", "8192"]
        n_out = 32
    prompts = make_prompts(args.seed, 200)
    dep = Deployment(args, "tp4")
    res: dict = {"phase": "serve_8b_mesh_1x4", "model": model, "requests": []}
    try:
        dep.start_store()
        w = dep.start_worker("worker", model, tok_path,
                             wargs + ["--mesh", "1,4"])
        dep.wait_worker(w, args.worker_timeout)
        res["worker_ready_s"] = round(w.ready_s, 2)
        dep.start_frontend(served)
        results: dict = {}
        run_wave(dep.http_port, served,
                 [("cold_chat", "chat", prompts["short_a"], n_out)], results)
        run_wave(dep.http_port, served, [
            ("chat", "chat", prompts["short_c"], n_out),
            ("completion", "completion", prompts["short_a"], n_out),
            ("chat_again", "chat", prompts["short_a"], n_out),
        ], results)
        rep = dep.engine_probe(w)
        holders = {"store": holds_accelerator(dep.store.p.pid),
                   "frontend": holds_accelerator(dep.frontend.p.pid)}
        rc = dep.drain_worker(w, 120)
        wlog = w.log_text()
    finally:
        dep.stop_all()
    failures = judge_requests(results, res)
    res["device"] = {"platform": rep["platform"], "kind": rep["device_kind"],
                     "count": rep["device_count"]}
    res["mesh_device_ids"] = rep["mesh_device_ids"]
    res["mesh_shape"] = rep["mesh_shape"]
    res["attention_traced"] = rep["attention"]
    res["memory"] = rep["memory"]
    res["compile"] = compile_totals(rep)
    res["engine_involuntary_remats_total"] = max(
        rep["compile"]["involuntary_remats_total"],
        count_remats(wlog))
    res["worker_exit_code_after_drain"] = rc
    used = [m["bytes_in_use"] for m in rep["memory"]]
    res["bytes_in_use_per_device"] = used
    res["bytes_in_use_tolerance"] = 0.10
    if len(used) != 4:
        failures.append(f"worker mesh holds {len(used)} devices, not 4")
    if all(u is not None for u in used) and used:
        mean = sum(used) / len(used)
        res["bytes_in_use_max_dev_from_mean"] = max(
            abs(u - mean) / mean for u in used)
        if res["bytes_in_use_max_dev_from_mean"] > 0.10:
            failures.append(f"memory not spread evenly: {used}")
    elif rep["platform"] == "tpu":
        failures.append("no memory_stats from the TPU devices")
    if res["engine_involuntary_remats_total"]:
        failures.append("involuntary remats > 0")
    if rc != 0:
        failures.append(f"worker exited {rc} after drain")
    failures += judge_control_plane(holders)
    res["ok"] = not failures
    if failures:
        res["failures"] = failures
        sys.stderr.write("--- tp4 worker log tail ---\n" + wlog[-4000:] + "\n")
    return res


def four_chip_replicas(args, tok_path: str) -> dict:
    """Two one-chip workers on distinct chips behind the KV router."""
    from dynamo_tpu.utils.device_env import one_chip_env

    model = "tiny" if args.rehearse else "1b"
    served = f"smoke-{model}"
    if args.rehearse:
        wargs = list(REHEARSE_WORKER_ARGS)
        n_out, long_bytes = 16, 200
        chip_env = lambda i: {}  # noqa: E731 — virtual CPU devices
    else:
        wargs = ["--num-blocks", "1024", "--max-model-len", "8192"]
        n_out, long_bytes = 32, 700
        chip_env = one_chip_env
    prompts = make_prompts(args.seed, long_bytes)
    shared = prompts["long_a"]
    dep = Deployment(args, "replicas")
    res: dict = {"phase": "replicas_kv_router", "model": model,
                 "requests": []}
    try:
        dep.start_store()
        ws = [dep.start_worker(f"worker{i}", model, tok_path, wargs,
                               chip_env(i)) for i in (0, 1)]
        reps = [dep.wait_worker(w, args.worker_timeout) for w in ws]
        dep.start_frontend(served, router_mode="kv")
        results: dict = {}
        # a shared-prefix pair, one after the other so the router has seen
        # the first one's blocks when it places the second
        run_wave(dep.http_port, served,
                 [("shared_1", "chat", shared + " alpha", n_out)], results)
        time.sleep(1.0)
        run_wave(dep.http_port, served,
                 [("shared_2", "chat", shared + " beta", n_out)], results)
        run_wave(dep.http_port, served, [
            ("other_1", "completion", prompts["short_a"], n_out),
            ("other_2", "completion", prompts["short_b"], n_out),
        ], results)
        after = [dep.engine_probe(w) for w in ws]
        holders = [holds_accelerator(w.p.pid) for w in ws]
        rcs = [dep.drain_worker(w) for w in ws]
    finally:
        dep.stop_all()
    failures = judge_requests(results, res)
    res["workers"] = [
        {"device_count_seen": r["device_count"], "platform": r["platform"],
         "mesh_device_ids": r["mesh_device_ids"],
         "accel_fds": h["accel_fds"],
         "compiles_total": a["compile"]["compiles_total"],
         "bytes_in_use": a["memory"][0]["bytes_in_use"], "drain_rc": rc}
        for r, a, h, rc in zip(reps, after, holders, rcs)]
    if not args.rehearse:
        for i, r in enumerate(reps):
            if r["device_count"] != 1:
                failures.append(
                    f"worker{i} sees {r['device_count']} chips, wanted 1")
        fds = [tuple(h["accel_fds"]) for h in holders]
        if fds[0] and fds[0] == fds[1]:
            failures.append(f"both workers hold the same device files {fds}")
    if any(rcs):
        failures.append(f"worker drain exit codes {rcs}")
    res["ok"] = not failures
    if failures:
        res["failures"] = failures
    return res


# -------------------------------- main --------------------------------------


def write_tokenizer(args) -> str:
    sys.path.insert(0, HERE)
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.llm.tokenizer import byte_tokenizer

    vocab = 512 if args.rehearse else ModelConfig.llama3_1b().vocab_size
    path = os.path.join(args.workdir, "tokenizer.json")
    with open(path, "w") as f:
        f.write(byte_tokenizer(vocab).to_json_str())
    return path


def parent_main(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "dynamo_tpu")):
        sys.stderr.write("chip_smoke FAILED: no dynamo_tpu/ beside this "
                         "script — it drives the repo it sits in\n")
        return EXIT_FAILED
    os.makedirs(args.logdir, exist_ok=True)
    device = None
    try:
        tok = write_tokenizer(args)
        if args.four_chips:
            res = four_chip_serve(args, tok)
            emit(res)
            if not res["ok"]:
                raise PhaseFailed("8b --mesh 1,4 serve phase failed")
            device = res["device"]
            run_child("compare", args, 900)
            rep = four_chip_replicas(args, tok)
            emit(rep)
            if not rep["ok"]:
                raise PhaseFailed("replicas phase failed")
            want = 4
        else:
            run_child("device", args, 900)
            res = serve_phase(args, tok)
            emit(res)
            if not res["ok"]:
                raise PhaseFailed("serve phase failed")
            device = res["device"]
            want = 1
    except PhaseFailed as e:
        sys.stderr.write(f"chip_smoke FAILED: {e}\n")
        return EXIT_FAILED
    if device["platform"] != "tpu":
        sys.stderr.write(
            f"chip_smoke: every phase passed, but on platform "
            f"{device['platform']!r} — this is a rehearsal, not a chip run\n")
        return EXIT_NOT_TPU
    if device["count"] != want:
        sys.stderr.write(f"chip_smoke: the worker saw {device['count']} "
                         f"chips, this run is for {want}\n")
        return EXIT_FAILED
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the four-chip phase (8b at --mesh 1,4, "
                         "1b tp4-vs-tp1 logits, two replicas behind the KV "
                         "router)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, tolerate a non-TPU platform to walk "
                         "the control flow; never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--worker-timeout", type=float, default=600.0)
    ap.add_argument("--logdir", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="where the started processes' logs go")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args.child, args.rehearse, args.seed)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        args.workdir = workdir
        return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
