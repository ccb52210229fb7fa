"""Router benchmark: KV-aware routing vs round-robin on prefix-structured
workloads (the in-tree reproduction of the reference's router benchmark —
its TTFT-class claims come from exactly this sweep).

Boots a self-contained fleet (store + N mocker workers + two frontends:
one round_robin, one kv) as real processes, then drives the SAME
prefix-structured dataset through both — each mode from a cold cache —
and reports TTFT percentiles, per-phase prefix-hit ratio, and cached
blocks/request per router mode.

    python -m benchmarks.router_bench --workers 2 --requests 64 \
        --prefix-ratio 0.8

Reading the numbers: KV-aware routing trades load balance for prefix
affinity, so it wins when prefill cost dominates queueing — real engines,
long ISLs, cache pressure. The mocker compresses service times by
``--speedup-ratio``, which shrinks the prefill savings while queueing
skew from affinity stays, so at high speedup ratios round-robin can show
lower TTFT even as the kv mode reports deeper cache matches
(cached_blocks_per_request). Sweep ``--speedup-ratio`` toward 1 and
``--prefix-ratio``/``--groups`` up to see the crossover; the routing hot
path itself costs ~90 us/request (see the microbenchmark in
tests/test_benchmarks.py's module history).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from .datagen import PrefixDatasetConfig, generate_prefix_dataset  # noqa: E402
from .loadgen import closed_loop  # noqa: E402


def _byte_tokenizer_json() -> str:
    from dynamo_tpu.llm.tokenizer import byte_tokenizer  # noqa: PLC0415

    return byte_tokenizer().to_json_str()


async def clear_worker_caches(store_addr: str) -> int:
    """Drop every worker's prefix cache (the clear_kv_blocks endpoint) so
    each router mode starts cold — without this, whichever mode runs
    second inherits a fully warmed fleet and the comparison is noise."""
    import msgpack

    from dynamo_tpu.runtime.context import Context  # noqa: PLC0415
    from dynamo_tpu.runtime.store import StoreClient  # noqa: PLC0415
    from dynamo_tpu.runtime.transport import TransportClient  # noqa: PLC0415

    client = await StoreClient.connect(store_addr)
    transport = TransportClient()
    cleared = 0
    try:
        for key, value in await client.get_prefix("v1/instances/"):
            if "/clear_kv_blocks/" not in key:
                continue
            rec = msgpack.unpackb(value, raw=False)
            async for _ in transport.generate(rec["addr"], {}, Context()):
                cleared += 1
                break
    finally:
        await transport.close()
        await client.close()
    return cleared


async def collect_cache_counters(
    store_addr: str, expect_workers: int, component: str = "backend",
) -> dict:
    """Per-worker cumulative (hits, queries) from the load-metrics subject.
    Counters are process-cumulative — callers subtract a baseline to get
    one benchmark phase's ratio. Waits for ``expect_workers`` DISTINCT
    workers (a stop-on-first-repeat heuristic returns a partial fleet when
    one worker publishes faster, corrupting the baseline subtraction)."""
    import msgpack

    from dynamo_tpu.runtime.store import StoreClient  # noqa: PLC0415

    client = await StoreClient.connect(store_addr)
    counters: dict = {}
    try:
        sub = await client.subscribe(f"v1/events/dynamo/{component}/")
        deadline = asyncio.get_running_loop().time() + 10.0
        while (len(counters) < expect_workers
               and asyncio.get_running_loop().time() < deadline):
            try:
                ev = await asyncio.wait_for(sub.next(), timeout=3.0)
            except asyncio.TimeoutError:
                break
            if not ev or ev.get("event") != "msg":
                continue
            if "load_metrics" not in ev.get("key", ""):
                continue
            snap = msgpack.unpackb(ev["value"], raw=False)
            counters[snap.get("worker_id")] = (
                snap.get("prefix_cache_hits", 0),
                snap.get("prefix_cache_queries", 0),
            )
        await sub.cancel()
        return counters
    finally:
        await client.close()


def hit_ratio_delta(before: dict, after: dict) -> float:
    hits = sum(h for h, _ in after.values()) - sum(
        h for h, _ in before.values())
    queries = sum(q for _, q in after.values()) - sum(
        q for _, q in before.values())
    return hits / queries if queries > 0 else 0.0


def run(argv=None) -> dict:
    p = argparse.ArgumentParser(description="router mode benchmark")
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--isl", type=int, default=256)
    p.add_argument("--osl", type=int, default=16)
    p.add_argument("--prefix-ratio", type=float, default=0.8)
    p.add_argument("--groups", type=int, default=8)
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--speedup-ratio", type=float, default=10.0,
                   help="mocker time compression")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=0,
                   help="per-worker KV blocks; 0 = auto-size to ~75%% of "
                        "the shared-prefix working set, so round-robin's "
                        "cross-worker duplication thrashes while KV-aware "
                        "partitioning fits (the regime the reference's "
                        "router benchmark demonstrates)")
    args = p.parse_args(argv)
    if args.num_blocks == 0:
        shared_blocks = args.groups * (
            int(args.isl * args.prefix_ratio) // args.block_size
        )
        per_seq = (args.isl + args.osl) // args.block_size + 2
        args.num_blocks = (int(shared_blocks * 0.75)
                           + per_seq * (args.concurrency + 1))

    import tempfile

    from utils import ManagedProcess, free_port  # noqa: PLC0415

    tok_path = Path(tempfile.mkstemp(suffix=".json")[1])
    tok_path.write_text(_byte_tokenizer_json())
    store_port = free_port()
    procs = []
    report: dict = {
        "workers": args.workers, "requests": args.requests,
        "isl": args.isl, "osl": args.osl,
        "prefix_ratio": args.prefix_ratio, "modes": {},
    }
    try:
        store = ManagedProcess(
            ["-m", "dynamo_tpu.runtime.store", "--host", "127.0.0.1",
             "--port", str(store_port)],
            name="store", ready_pattern=r"listening",
        )
        procs.append(store)
        store.wait_ready(20)
        env = {"DYNTPU_STORE_ADDR": f"127.0.0.1:{store_port}"}
        for i in range(args.workers):
            m = ManagedProcess(
                ["-m", "dynamo_tpu.mocker", "--model-name", "mock",
                 "--tokenizer", str(tok_path),
                 "--block-size", str(args.block_size),
                 "--num-blocks", str(args.num_blocks),
                 "--max-model-len", str(args.isl + args.osl + 64),
                 "--speedup-ratio", str(args.speedup_ratio)],
                name=f"mocker{i}", env=env, ready_pattern=r"mocker ready",
            )
            procs.append(m)
        for m in procs[1:]:
            m.wait_ready(60)

        dataset = generate_prefix_dataset(PrefixDatasetConfig(
            num_requests=args.requests, isl=args.isl,
            prefix_ratio=args.prefix_ratio, groups=args.groups,
            vocab_size=200, vocab_offset=10,
        ))
        store_addr = f"127.0.0.1:{store_port}"
        for mode in ("round_robin", "kv"):
            asyncio.run(clear_worker_caches(store_addr))
            baseline = asyncio.run(collect_cache_counters(
                store_addr, args.workers))
            http_port = free_port()
            frontend = ManagedProcess(
                ["-m", "dynamo_tpu.frontend", "--host", "127.0.0.1",
                 "--port", str(http_port), "--router-mode", mode],
                name=f"frontend-{mode}", env=env,
                ready_pattern=r"frontend ready",
            )
            procs.append(frontend)
            frontend.wait_ready(30)
            summary = asyncio.run(closed_loop(
                f"http://127.0.0.1:{http_port}", "mock", dataset,
                args.osl, args.concurrency,
            ))
            after = asyncio.run(collect_cache_counters(
                store_addr, args.workers))
            summary["prefix_hit_ratio"] = round(
                hit_ratio_delta(baseline, after), 4
            )
            # hits/queries is biased toward 1 (the scheduler stops querying
            # at the first miss, so a fully-cold request contributes one
            # query); matched-blocks-per-request compares cleanly across
            # modes on the same dataset
            hits_delta = (sum(h for h, _ in after.values())
                          - sum(h for h, _ in baseline.values()))
            summary["cached_blocks_per_request"] = round(
                hits_delta / max(summary["completed"], 1), 2
            )
            report["modes"][mode] = summary
            frontend.terminate()
            procs.remove(frontend)

        rr = report["modes"]["round_robin"]
        kv = report["modes"]["kv"]
        if kv["ttft_avg_ms"] > 0:
            report["kv_ttft_speedup"] = round(
                rr["ttft_avg_ms"] / kv["ttft_avg_ms"], 2
            )
    finally:
        for p_ in reversed(procs):
            try:
                p_.terminate()
            except Exception:
                pass
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    run()
