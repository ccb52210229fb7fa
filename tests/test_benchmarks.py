"""Benchmark harness: data/load generators (unit) + the load driver and
router benchmark against a real mocker fleet (e2e).
(ref coverage: benchmarks/data_generator tests + router benchmark)"""

import json
import sys
from collections import Counter
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks.datagen import (  # noqa: E402
    LoadSchedule, PrefixDatasetConfig, generate_prefix_dataset,
)

from test_llm_pipeline import byte_tokenizer  # noqa: E402
from utils import ManagedProcess, free_port  # noqa: E402


# ------------------------------ unit ----------------------------------


def test_prefix_dataset_sharing_structure():
    cfg = PrefixDatasetConfig(num_requests=64, isl=120, prefix_ratio=0.5,
                              groups=3, branches=2, seed=1)
    ds = generate_prefix_dataset(cfg)
    assert len(ds) == 64
    assert all(len(r.token_ids) == 120 for r in ds)
    shared = int(120 * 0.5)
    group_len = (shared * 2) // 3
    # same group → identical leading group_len tokens
    by_group = {}
    for r in ds:
        by_group.setdefault(r.group, []).append(r)
    for rs in by_group.values():
        heads = {tuple(r.token_ids[:group_len]) for r in rs}
        assert len(heads) == 1
    # different groups → different heads
    heads = {g: tuple(rs[0].token_ids[:group_len])
             for g, rs in by_group.items()}
    assert len(set(heads.values())) == len(heads)
    # tails are unique (no accidental full duplication)
    tails = [tuple(r.token_ids[shared:]) for r in ds]
    assert len(set(tails)) == len(tails)


def test_prefix_ratio_zero_is_fully_random():
    ds = generate_prefix_dataset(PrefixDatasetConfig(
        num_requests=8, isl=64, prefix_ratio=0.0))
    assert len({tuple(r.token_ids[:16]) for r in ds}) == 8


def test_sin_schedule_modulates_rate():
    sched = LoadSchedule(kind="sin", rate=50.0, duration_s=20.0,
                         period_s=20.0, amplitude=0.9, seed=0)
    times = sched.arrival_times()
    assert times == sorted(times)
    # first half-period runs hot, second half-period runs cold
    counts = Counter(int(t // 5) for t in times)
    assert counts[0] + counts[1] > 2.5 * (counts[2] + counts[3])
    # constant schedule lands near rate * duration
    n_const = len(LoadSchedule(kind="constant", rate=50.0,
                               duration_s=20.0).arrival_times())
    assert 800 < n_const < 1200


# ------------------------------- e2e ----------------------------------


@pytest.fixture(scope="module")
def tokenizer_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("tok") / "tokenizer.json"
    path.write_text(byte_tokenizer().to_json_str())
    return str(path)


@pytest.fixture
def mock_cluster(tokenizer_file):
    store_port = free_port()
    http_port = free_port()
    procs = []
    store = ManagedProcess(
        ["-m", "dynamo_tpu.runtime.store", "--host", "127.0.0.1",
         "--port", str(store_port)],
        name="store", ready_pattern=r"listening",
    )
    procs.append(store)
    store.wait_ready(20)
    env = {"DYNTPU_STORE_ADDR": f"127.0.0.1:{store_port}"}
    mocker = ManagedProcess(
        ["-m", "dynamo_tpu.mocker", "--model-name", "mock",
         "--tokenizer", tokenizer_file, "--block-size", "16",
         "--num-blocks", "2048", "--max-model-len", "512",
         "--speedup-ratio", "50"],
        name="mocker", env=env, ready_pattern=r"mocker ready",
    )
    procs.append(mocker)
    mocker.wait_ready(60)
    frontend = ManagedProcess(
        ["-m", "dynamo_tpu.frontend", "--host", "127.0.0.1",
         "--port", str(http_port)],
        name="frontend", env=env, ready_pattern=r"frontend ready",
    )
    procs.append(frontend)
    frontend.wait_ready(30)
    yield f"http://127.0.0.1:{http_port}"
    for p in reversed(procs):
        p.terminate()


@pytest.mark.anyio
async def test_loadgen_closed_loop(mock_cluster):
    from benchmarks.datagen import PrefixDatasetConfig
    from benchmarks.loadgen import closed_loop

    ds = generate_prefix_dataset(PrefixDatasetConfig(
        num_requests=12, isl=128, vocab_size=200, vocab_offset=10))
    report = await closed_loop(mock_cluster, "mock", ds, osl=8,
                               concurrency=4)
    assert report["completed"] == 12
    assert report["errors"] == 0
    assert report["output_tok_s"] > 0
    assert report["ttft_p50_ms"] > 0


@pytest.mark.anyio
async def test_loadgen_open_loop_sin(mock_cluster):
    from benchmarks.datagen import PrefixDatasetConfig
    from benchmarks.loadgen import open_loop

    ds = generate_prefix_dataset(PrefixDatasetConfig(
        num_requests=64, isl=64, vocab_size=200, vocab_offset=10))
    report = await open_loop(
        mock_cluster, "mock", ds, 4,
        LoadSchedule(kind="sin", rate=6.0, duration_s=5.0, period_s=5.0,
                     amplitude=0.8),
    )
    assert report["completed"] > 0
    assert report["errors"] == 0
    assert "sin" in report["mode"]


def test_router_bench_end_to_end():
    """The full router benchmark: kv mode must produce a higher prefix-hit
    ratio than round-robin on a high-reuse workload."""
    from benchmarks.router_bench import run

    report = run([
        "--workers", "2", "--requests", "24", "--isl", "128",
        "--osl", "8", "--prefix-ratio", "0.9", "--concurrency", "4",
        "--speedup-ratio", "50",
    ])
    rr = report["modes"]["round_robin"]
    kv = report["modes"]["kv"]
    assert rr["completed"] == 24 and kv["completed"] == 24
    assert rr["errors"] == 0 and kv["errors"] == 0
    assert "kv_ttft_speedup" in report
