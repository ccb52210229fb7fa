#!/usr/bin/env python3
"""The chip benchmark: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>
    python3 benchmarks/chip/run.py --list
    python3 benchmarks/chip/run.py --workload <cell> --rehearse   # CPU, tiny

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<name>.json``) under a traffic mix (``traffic/<name>.json``).  The
run starts store + worker + frontend as separate processes, warms every step
program the cell's shape can reach, fills what the traffic needs, then lets
the load generator (its own process) drive HTTP traffic whose shape is the
same in every run; the measured window is cut out of steady state.  Every
number a user feels is taken on the client's clock.  The last line of stdout
is the result object; earlier lines are for people.

This process never imports JAX: the worker is the only process on the chip.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import client as C            # noqa: E402
from benchmarks.chip import deploy as D            # noqa: E402
from benchmarks.chip import metrics as M           # noqa: E402
from benchmarks.chip import peaks as P             # noqa: E402
from benchmarks.chip import shape as S             # noqa: E402
from benchmarks.chip import worker_launch as WL    # noqa: E402

EXIT_FAILED = 1
EXIT_NO_CHIP = 3
SERVED = "bench"
WARM_TIMEOUT_S = 600.0
READY_TIMEOUT_S = 900.0     # a cold worker compiles before it is ready
RUN_DIR = os.path.join(HERE, ".runs")


def say(tag: str, obj) -> None:
    print(json.dumps({tag: obj}), flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_reader(name: str):
    """A per-layer metric is ``layer_metrics/<name>.py``: SOURCE, LAYER,
    UNIT, BETTER, MOVES and ``read(ctx) -> float | None``."""
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def engine_dict(eargs: list) -> dict:
    """The engine's bucket lists and limits, from the program's own
    ``EngineConfig`` (no JAX) and the configuration's worker arguments."""
    from dynamo_tpu.engine.config import EngineConfig

    a = dict(zip(eargs[::2], eargs[1::2]))
    e = EngineConfig()
    return {"block_size": int(a["--block-size"]),
            "num_blocks": int(a["--num-blocks"]),
            "max_num_seqs": int(a["--max-num-seqs"]),
            "max_batched_tokens": int(a["--max-batched-tokens"]),
            "max_model_len": int(a["--max-model-len"]),
            "prefill_buckets": list(e.prefill_buckets),
            "decode_buckets": list(e.decode_buckets)}


# ------------------------------ --list --------------------------------------


def do_list() -> int:
    bench = load_benchmark()
    ok = True
    for c in bench["configs"]:
        cfg = load_config(c["name"])
        say("config", {"name": c["name"], "file": c["file"],
                       "chips": cfg["chips"], "reduced": c["reduced"]})
    for w in bench["workloads"]:
        mix = S.load_mix(w["traffic"])
        say("cell", {"name": w["name"], "config": w["config"],
                     "traffic": w["traffic"], "chips": w["chips"],
                     "loop": mix["loop"],
                     "end_to_end": [m["name"] for m in
                                    metrics_of(bench, w["name"],
                                               "end_to_end")],
                     "per_layer": [m["name"] for m in
                                   metrics_of(bench, w["name"],
                                              "per_layer")]})
    declared = {m["name"]: m for m in bench["per_layer"]}
    found = sorted(f[:-3] for f in os.listdir(
        os.path.join(HERE, "layer_metrics"))
                   if f.endswith(".py") and not f.startswith("_"))
    for name in found:
        r = load_reader(name)
        d = declared.get(name)
        agree = d is not None and all(
            d[k] == getattr(r, k.upper()) for k in
            ("unit", "better", "source", "layer", "moves"))
        ok = ok and agree
        say("reader", {"name": name, "source": r.SOURCE, "layer": r.LAYER,
                       "unit": r.UNIT, "moves": r.MOVES,
                       "declared_in_BENCHMARK_json": d is not None,
                       "agrees": agree})
    for name in declared:
        if name not in found:
            ok = False
            say("reader_missing", name)
    mixes = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
                   if f.endswith(".json"))
    say("traffic_files", mixes)
    say("config_files", sorted(f[:-5] for f in os.listdir(
        os.path.join(HERE, "configs")) if f.endswith(".json")))
    return 0 if ok else 1


# ------------------------------ warm-up -------------------------------------


async def _send(session, port, toks, max_tokens):
    rec: dict = {"due_t": time.monotonic()}
    try:
        await asyncio.wait_for(C.stream_completion(
            session, port, SERVED, toks, max_tokens, rec), WARM_TIMEOUT_S)
    except asyncio.TimeoutError:
        raise D.DeployFailed(f"warm-up request of {len(toks)} tokens got no "
                             f"answer in {WARM_TIMEOUT_S}s")
    if not M.request_ok(rec):
        raise D.DeployFailed(f"warm-up request failed: "
                             f"{ {k: rec.get(k) for k in ('status', 'done', 'errors', 'completion_tokens', 'max_tokens')} }")
    return rec


async def warm_up(dep, shape, eng, seed, vocab, steps: list) -> None:
    """One request per step program the shape can reach, derived from the
    shape and the engine's bucket lists.  A (T, W) prefill program is
    reached by a prompt whose first ``W*16 - T`` tokens are already cached
    (the head of one long base prompt sent first) plus T fresh tokens: the
    prefix cache starts the chunk where the hit ends.  A decode bucket is
    reached by that many short requests at once, each long enough to be
    decoding still when the last of them has been admitted."""
    bs = eng["block_size"]
    progs = S.reachable_prefill_programs(shape, eng)
    buckets = S.reachable_decode_buckets(shape, eng)
    port = dep.http_port

    def compiles():
        return dep.engine_probe()["compile"]["compiles_total"]

    async with C.new_session() as session:
        async def step(name, coro):
            t0 = time.monotonic()
            await coro
            dep.check_alive()
            steps.append({"step": name,
                          "s": round(time.monotonic() - t0, 3),
                          "compiles_total": compiles()})

        # a chunk of T tokens that ends at token `end(W)` runs (T, W)
        def end(W):
            return min(W * bs, eng["max_model_len"] - 2 * bs)

        wmax = max(W for _, W in progs)
        base = S.tokens_for(seed, "warm", 0, end(wmax), vocab)
        if any(end(W) - T > 0 for T, W in progs):
            await step(f"base_{len(base)}", _send(session, port, base, 2))
        for i, (T, W) in enumerate(progs):
            head = max(0, end(W) - T)
            toks = base[:head] + S.tokens_for(seed, "warm", 1 + i,
                                              end(W) - head, vocab)
            await step(f"prefill_T{T}_W{W}", _send(session, port, toks, 2))
        lens = sorted({r["total_len"] for r in shape["requests"]})
        short = lens[0]
        # the engine's control-state update is a program per power of two
        # of the rows that change in one step: b requests that end together
        # reach the one for b rows, so the small powers get bursts too (a
        # window in which 2 or 4 requests ended together compiled it: PR 27)
        small = [b for b in (2, 4) if not buckets or b < buckets[0]]
        for b in small + buckets:
            # the b requests must all still be decoding when the last one
            # joins (a step admits max_batched_tokens of prompts; twice the
            # steps that takes, and 8 more), or the bucket's program is left
            # to compile in the ramp (on four chips the one for 64 rows took
            # 17.7 s and ran 8 s into the window: PR 27)
            n_tok = 2 * -(-b * short // eng["max_batched_tokens"]) + 8
            burst = [_send(session, port,
                           S.tokens_for(seed, "warm", 1000 + 100 * b + j,
                                        short, vocab), n_tok)
                     for j in range(b)]
            await step(f"decode_B{b}", asyncio.gather(*burst))
        if shape["docs"]:
            # the fill the traffic needs: every document once, least
            # popular first so the popular ones are the most recently used
            async def fill():
                for d in reversed(range(len(shape["docs"]))):
                    await _send(session, port,
                                S.doc_tokens(shape, d, seed, vocab), 1)
            await step("fill_documents", fill())


# ------------------------------ the run -------------------------------------


def write_tokenizer(path: str, vocab: int) -> None:
    from dynamo_tpu.llm.tokenizer import byte_tokenizer   # no JAX

    with open(path, "w") as f:
        f.write(byte_tokenizer(vocab).to_json_str())


def read_jsonl(path: str) -> list:
    out = []
    if os.path.exists(path):
        with open(path, errors="replace") as f:
            for ln in f:
                ln = ln.strip()
                if ln:
                    try:
                        out.append(json.loads(ln))
                    except ValueError:
                        pass
    return out


def reduce_trace(profile_dir: str, rundir: str, env: dict):
    """The device trace is reduced in a process of its own, on the CPU,
    after the worker has let go of the chip."""
    files = []
    for dp, _, fns in os.walk(profile_dir):
        files += [os.path.join(dp, f) for f in fns if f.endswith(".xplane.pb")]
    if not files:
        return None
    out = os.path.join(rundir, "trace_summary.json")
    e = dict(env)
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-m", "benchmarks.chip.xplane",
                        sorted(files)[-1], out], env=e, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    if p.returncode != 0 or not os.path.exists(out):
        say("trace_reduce_failed", (p.stderr or "")[-2000:])
        return None
    with open(out) as f:
        return json.load(f)


class NoChip(Exception):
    pass


def launch(cell: dict, cfg: dict, args, rundir: str, setup: dict):
    """Start store + worker + frontend for ``cell`` and wait until the
    worker has built its engine and passed (or failed) the reference check.
    Returns (deployment, child environment, device, reference verdict)."""
    rehearse = args.rehearse
    vocab = cfg["rehearse"]["vocab_size"] if rehearse else cfg["vocab_size"]
    cfg_path = os.path.join(HERE, "configs", f"{cell['config']}.json")
    try:
        # a field the program lacks or a reference that is not there stops
        # the run here, before a process is started or the chip is asked for
        WL.check_configuration(cfg, rehearse, cfg_path)
    except WL.ConfigError as e:
        raise D.DeployFailed(str(e))
    shutil.rmtree(rundir, ignore_errors=True)
    os.makedirs(rundir)
    cache = os.path.abspath(args.cache_dir)
    os.makedirs(os.path.join(cache, "jax"), exist_ok=True)
    env = dict(os.environ)
    env.update({
        "JAX_COMPILATION_CACHE_DIR": os.path.join(cache, "jax"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        "DYNTPU_AUTOTUNE_CACHE": os.path.join(cache, "autotune.json"),
        "BENCH_CHIP_RUNDIR": rundir,
        "BENCH_CHIP_WEIGHT_SEED": str(args.seed % (2 ** 31 - 1)),
        "DYNTPU_OBS_PROFILE_DIR": os.path.join(rundir, "profile"),
        "TPU_LOG_DIR": "disabled",
    })
    worker_env = {}
    if args.trace:
        # counts and spans are read in the traced run only
        worker_env = {
            "DYNTPU_OBS_STEPSTATS_PATH": os.path.join(rundir,
                                                      "stepstats.jsonl"),
            "DYNTPU_TRACE_SAMPLE_RATIO": "1",
            "DYNTPU_TRACE_EXPORT_PATH": os.path.join(rundir, "spans.jsonl"),
        }
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{int(cell['chips'])}")
    tok_path = os.path.join(rundir, "tokenizer.json")
    write_tokenizer(tok_path, vocab)
    dep = D.Deployment(rundir, env)
    setup["launch_s"] = round(time.monotonic() - T_START, 3)
    try:
        dep.start_all(cfg_path, SERVED, tok_path, worker_env, rehearse)
        rep = dep.wait_ready(SERVED, READY_TIMEOUT_S)
        device = {"platform": rep["platform"], "kind": rep["device_kind"],
                  "count": rep["device_count"]}
        if not rehearse:
            if device["platform"] != "tpu":
                raise NoChip(f"no TPU: worker runs on {device}")
            if device["count"] < int(cell["chips"]):
                raise NoChip(f"cell needs {cell['chips']} chips, worker "
                             f"sees {device['count']}")
            P.peaks_for(device["kind"])       # unknown kind is an error
        with open(os.path.join(rundir, "reference.json")) as f:
            ref = json.load(f)
    except BaseException:
        dep.stop_all()
        raise
    setup["processes_up_s"] = round(time.monotonic() - T_START, 3)
    setup["worker"] = ref["stamps"]
    setup["compile_at_ready"] = rep["compile"]["compiles_total"]
    return dep, env, device, ref["verdict"]


def drive(dep, shape: dict, seed: int, vocab: int, rundir: str,
          trace: int, tag: str = "", chips: int = 1) -> dict:
    """Let the load generator (its own process) run ``shape``; returns its
    records, the window's bounds on the shared monotonic clock, and the
    worker's probe at window open, close and after the tail."""
    plan = {"shape": shape, "seed": seed, "vocab": vocab,
            "port": dep.http_port, "model": SERVED,
            "t0": time.monotonic() + 1.0}
    plan_path = os.path.join(rundir, f"plan{tag}.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    out_path = os.path.join(rundir, f"loadgen_out{tag}.json")
    log_path = os.path.join(rundir, f"loadgen{tag}.log")
    w0 = plan["t0"] + shape["ramp_s"]
    w1 = w0 + float(shape["seconds"])
    prof = None
    with open(log_path, "w") as lg_log:
        loadgen = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.chip.loadgen", plan_path,
             out_path], env=dict(dep.env), cwd=ROOT, stdout=lg_log,
            stderr=subprocess.STDOUT)
        try:
            time.sleep(max(0.0, w0 - time.monotonic()))
            dep.check_alive()
            h0 = dep.engine_probe()
            if trace:
                # one capture's events grow with the device planes: on four
                # chips a 3 s capture was not written out in 120 s (PR 27)
                ms = int(min(3000, max(200, shape["seconds"] * 1000 / 3))
                         / chips)
                time.sleep(max(0.0, (w0 + w1) / 2 - ms / 2000.0
                               - time.monotonic()))
                _, prof = D.http_json(dep.sys_port, "GET",
                                       f"/debug/profile?ms={ms}",
                                       timeout=240)
                say("profile", prof)
            time.sleep(max(0.0, w1 - time.monotonic()))
            dep.check_alive()
            h1 = dep.engine_probe()
            rc = loadgen.wait(S.TAIL_S + 60)
        finally:
            if loadgen.poll() is None:
                loadgen.kill()
                loadgen.wait()
    if rc != 0:
        with open(log_path, errors="replace") as f:
            raise D.DeployFailed("load generator failed:\n" + f.read()[-3000:])
    with open(out_path) as f:
        lg = json.load(f)
    return {"lg": lg, "w0": w0, "w1": w1, "h0": h0, "h1": h1, "prof": prof,
            "h2": dep.engine_probe()}


def cell_parts(args):
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[args.workload]
    cfg = load_config(cell["config"])
    mix = S.load_mix(cell["traffic"])
    rehearse = args.rehearse
    eargs = cfg["rehearse"]["engine_args"] if rehearse else cfg["engine_args"]
    vocab = cfg["rehearse"]["vocab_size"] if rehearse else cfg["vocab_size"]
    return bench, cell, cfg, mix, engine_dict(eargs), vocab


def shape_for(mix, args, rate=None, seconds=None) -> dict:
    return S.build_shape(
        mix, float(args.seconds if seconds is None else seconds), rate=rate,
        length_scale=8 if args.rehearse else 1,
        ramp_s=4.0 if args.rehearse else None)


def run_cell(args) -> int:
    bench, cell, cfg, mix, eng, vocab = cell_parts(args)
    chips = int(cell["chips"])
    rehearse = args.rehearse
    shape = shape_for(mix, args)
    say("shape", {"cell": cell["name"], "loop": shape["loop"],
                  "rate": shape["rate"], "clients": shape["clients"],
                  "ramp_s": shape["ramp_s"], **shape["summary"]})
    rundir = os.path.join(RUN_DIR,
                          f"{cell['name']}-s{args.seed}-t{args.trace}")
    setup: dict = {}
    try:
        dep, env, device, verdict = launch(cell, cfg, args, rundir, setup)
    except NoChip as e:
        sys.stderr.write(f"{e}\n")
        return EXIT_NO_CHIP
    except D.DeployFailed as e:
        sys.stderr.write(f"benchmark run failed: {e}\n")
        return EXIT_FAILED
    try:
        say("reference", verdict)
        steps: list = []
        t_w = time.monotonic()
        asyncio.run(warm_up(dep, shape, eng, args.seed, vocab, steps))
        setup["warm_up_s"] = round(time.monotonic() - t_w, 3)
        setup["warm_up_steps"] = steps
        setup["ramp_s"] = shape["ramp_s"]
        got = drive(dep, shape, args.seed, vocab, rundir, args.trace,
                    chips=chips)
    except D.DeployFailed as e:
        sys.stderr.write(f"benchmark run failed: {e}\n")
        return EXIT_FAILED
    finally:
        dep.stop_all()
    lg, w0, w1 = got["lg"], got["w0"], got["w1"]
    with open(dep.worker.log_path, errors="replace") as f:
        remat_lines = f.read().count("Involuntary full rematerialization")
    h0, h1, h2, prof = got["h0"], got["h1"], got["h2"], got["prof"]
    setup_s = w0 - T_START
    client = M.reduce_client(lg["records"], w0, w1, chips,
                             shape["loop"] == "open")
    window_compiles = (h1["compile"]["compiles_total"]
                      - h0["compile"]["compiles_total"])
    # what compiled between window open and close, by the program's own
    # label: [count, seconds]
    by0, by1 = (h["compile"].get("compiles_by_fn", {}) for h in (h0, h1))
    secs0, secs1 = (h["compile"].get("compile_secs_by_fn", {})
                    for h in (h0, h1))
    compiled = {fn: [n - by0.get(fn, 0),
                     round(secs1.get(fn, 0.0) - secs0.get(fn, 0.0), 3)]
                for fn, n in by1.items() if n > by0.get(fn, 0)}
    mem = [m["peak_bytes_in_use"] for m in h2["memory"]
           if m.get("peak_bytes_in_use")]
    device["memory_peak_bytes"] = max(mem) if mem else 0
    say("setup", {"setup_s": setup_s, **setup})
    untimed = sum(abs(r.get("untimed_tokens", 0)) for r in lg["records"])
    say("window", {"w0": w0, "w1": w1, "window_compiles": window_compiles,
                   "compiled_in_window": compiled,
                   "compile_cache": h2["compile_cache"],
                   "laps_max": lg["laps_max"],
                   "involuntary_remats":
                       h2["compile"].get("involuntary_remats_total"),
                   "remat_lines_in_worker_log": remat_lines,
                   "crashed": lg["crashed"], "untimed_tokens": untimed,
                   "served_out_tok_s_all_chips":
                       client["tokens_in_window"] / float(args.seconds),
                   **{k: client[k] for k in ("attempted", "failed",
                                             "completed", "n_ttft", "n_tpot",
                                             "n_gaps", "gen_lag_p99_ms",
                                             "longest_silence_ms")}})

    values = dict(client)
    values["setup_s"] = setup_s
    correct = (client["failed"] == 0 and client["attempted"] > 0
               and bool(verdict.get("ok")) and not lg["crashed"])
    result = {"correct": correct, "attempted": client["attempted"],
              "failed": client["failed"], "metrics": {}, "device": device}
    # a rehearsal's numbers never stand under a metric's own name
    pre = "rehearsal." if rehearse else ""
    if rehearse:
        result["rehearsal"] = True

    if not args.trace:
        for m in metrics_of(bench, cell["name"], "end_to_end"):
            v = values.get(m["name"])
            if v is not None:
                result["metrics"][pre + m["name"]] = {"value": v,
                                                      "unit": m["unit"]}
    else:
        trace = None
        if prof and prof.get("trace_dir"):
            trace = reduce_trace(prof["trace_dir"], rundir, env)
        steprecs = [r for r in read_jsonl(os.path.join(rundir,
                                                       "stepstats.jsonl"))
                    if w0 <= r.get("t_dispatch", 0) < w1]
        first_in = [r for r in lg["records"] if r.get("events")
                    and w0 <= r["events"][0][0] < w1]
        say("prefill_tokens", {
            "prompt_tokens_of_first_tokens_in_window":
                sum(r["prompt_tokens_sent"] for r in first_in),
            "stepstats_prefill_real_tokens":
                sum(r["real_tokens"] for r in steprecs
                    if r.get("kind") == "prefill"),
            "lapped_requests": sum(1 for r in first_in if r.get("lap"))})
        ctx = {"client": client, "records": lg["records"],
               "window": (w0, w1), "steps": steprecs,
               "spans": read_jsonl(os.path.join(rundir, "spans.jsonl")),
               "trace": trace, "health_end": h2,
               "window_compiles": window_compiles, "shape": shape,
               "config": cfg, "engine": eng, "chips": chips,
               "rehearse": rehearse,
               "peaks": None if rehearse else P.peaks_for(device["kind"])}
        for m in metrics_of(bench, cell["name"], "per_layer"):
            reader = load_reader(m["name"])
            if rehearse and reader.SOURCE == "device_trace":
                continue      # no CPU number under a device metric's name
            v = reader.read(ctx)
            if v is not None:
                result["metrics"][pre + m["name"]] = {"value": v,
                                                      "unit": m["unit"]}
        if trace and not rehearse:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                                   "idle_gaps": trace["idle_gaps"][:10]}
    if not args.keep:
        shutil.rmtree(os.path.join(rundir, "profile"), ignore_errors=True)
    print(json.dumps(result), flush=True)
    # each number `correct` compared, beside its limit
    sys.stderr.write(json.dumps({"compared": {
        "failed": [client["failed"], 0],
        "load_generator_crashed": [bool(lg["crashed"]), False],
        "reference": verdict.get("reference"),
        "reference_ok": [bool(verdict.get("ok")), True],
        **{f"{ph}.rel": [verdict[ph]["rel"], verdict.get("rel_tol")]
           for ph in ("prefill", "decode") if ph in verdict},
        **{c["stat"]: [c["value"], c["limit"]]
           for c in verdict.get("limits", [])},
        **({"error": verdict["error"]} if "error" in verdict else {})}})
        + "\n")
    if rehearse:
        return EXIT_NO_CHIP     # a rehearsal can never pass for a chip run
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny preset, lengths / 8: control flow only; "
                         "exits 3 and marks the line as a rehearsal")
    ap.add_argument("--cache-dir", default=os.path.join(HERE, ".cache"),
                    help="compile + autotune caches (fixed path in the "
                         "checkout by default)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the profiler trace in the run directory")
    args = ap.parse_args()
    if args.list:
        return do_list()
    if not args.workload:
        ap.error("--workload is required")
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        sys.stderr.write("no dynamo_tpu/ beside benchmarks/: the benchmark "
                         "drives the repo it sits in\n")
        return EXIT_FAILED
    if args.seconds is None:
        args.seconds = 6.0 if args.rehearse else float(
            load_benchmark()["run_seconds"])
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
