"""Which attention implementation and tile a step program is traced with.

One decision, made from the configuration and the mesh alone
(``engine.model.resolve_attention_impl`` / ``attention_choice``, the tile
from ``ops.paged_attention.default_kv_tile``): nothing is timed or swept
when an engine starts, ``attention_impl`` has two values, and what the chip
benchmark's configuration files pass still builds an ``EngineConfig``
through the worker's own parser.
"""

import asyncio
import json
from pathlib import Path

import pytest

import dynamo_tpu.ops.paged_attention as pa
import dynamo_tpu.worker as worker
from dynamo_tpu.engine import model as model_lib
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine, Request

CONFIGS = Path(__file__).parent.parent / "benchmarks" / "chip" / "configs"


def _engine_config(**kw):
    return EngineConfig(block_size=4, num_blocks=64, max_num_seqs=4,
                        max_num_batched_tokens=64, max_model_len=128,
                        decode_buckets=(4, 8), prefill_buckets=(16,), **kw)


@pytest.mark.anyio
@pytest.mark.parametrize("impl", ["pallas", "einsum"])
async def test_engine_starts_without_running_attention(impl, monkeypatch):
    """A cold start launches no attention kernel and traces no attention at
    all (no probe, no sweep); what it says it chose is what the first
    decode window is then traced with."""
    launches = []
    for name in ("paged_attention_decode", "paged_attention_ragged"):
        real = getattr(pa, name)

        def counted(*a, _real=real, _name=name, **kw):
            launches.append(_name)
            return _real(*a, **kw)

        monkeypatch.setattr(pa, name, counted)
    model_lib.ATTENTION_TRACES.clear()
    cfg = _engine_config(attention_impl=impl)
    engine = InferenceEngine(ModelConfig.tiny(), cfg)
    assert launches == [] and model_lib.ATTENTION_TRACES == {}

    choice = engine.attention_impl_choice
    tile = model_lib.decode_kv_tile(ModelConfig.tiny(), cfg, engine.mesh)
    assert choice["impl"] == {
        "decode": impl, "spec": "einsum", "prefill": "einsum"}
    assert choice["tiles"]["decode"] == (
        [1, tile] if impl == "pallas" else [0, 0])
    assert (tile > 0) == (impl == "pallas")
    assert engine.device_report()["attention_choice"] == choice

    await engine.start()
    try:
        req = Request(request_id=f"sel-{impl}", token_ids=[5, 6, 7],
                      max_tokens=3, temperature=0.0, ignore_eos=True)
        assert len([o async for o in engine.submit(req)]) == 3
    finally:
        await engine.stop()
    for cls, traced in model_lib.ATTENTION_TRACES.items():
        assert traced["impl"] == choice["impl"][cls]
        assert traced["tile"] == choice["tiles"][cls]
    assert "decode" in model_lib.ATTENTION_TRACES
    # the decode wrapper launches the one ragged kernel; einsum none
    assert ("paged_attention_decode" in launches) == (impl == "pallas")
    assert launches or impl == "einsum"


def test_largest_rung_stall_rebuilds_the_window_on_einsum():
    """The stall watchdog's second program for the largest decode bucket:
    the same window on ``attention_impl="einsum"``, once."""
    engine = InferenceEngine(ModelConfig.tiny(), _engine_config())
    assert engine._decode_kv_tile > 0
    engine._quarantine_shape(("decode", 8))
    assert engine._stall_einsum_fallback and not engine._shape_quarantine
    assert engine._decode_kv_tile == 0
    assert engine.attention_impl_choice["impl"]["decode"] == "einsum"
    assert engine.attention_impl_choice["tiles"]["decode"] == [0, 0]
    engine._quarantine_shape(("decode", 8))     # a second time: quarantined
    assert ("decode", 8) in engine._shape_quarantine


def test_engine_config_refuses_auto():
    with pytest.raises(ValueError, match="attention_impl"):
        EngineConfig(attention_impl="auto")


def test_worker_refuses_attention_impl_auto(capsys):
    with pytest.raises(SystemExit):
        worker.parse_args(["--attention-impl", "auto"])
    assert "--attention-impl" in capsys.readouterr().err


class _Built(Exception):
    pass


@pytest.mark.parametrize("rehearse", [False, True],
                         ids=["engine_args", "rehearse"])
@pytest.mark.parametrize("name", sorted(p.stem for p in CONFIGS.glob("*.json")))
def test_benchmark_engine_args_build_an_engine_config(
        name, rehearse, monkeypatch):
    """The seam to the chip benchmark: the launcher hands a configuration
    file's ``engine_args`` (``rehearse.engine_args`` on the CPU) to
    ``worker.main`` and replaces ``InferenceEngine`` to see the result; so
    does this test, stopping where the engine would be built."""
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    eargs = cfg["rehearse"]["engine_args"] if rehearse else cfg["engine_args"]
    seen = {}

    def stop_here(model_config, engine_config, params=None):
        seen["engine_config"] = engine_config
        raise _Built

    monkeypatch.setattr(worker, "InferenceEngine", stop_here)
    args = worker.parse_args(["--model", "tiny"] + list(eargs))
    with pytest.raises(_Built):
        asyncio.run(worker.run_worker(args))
    ec = seen["engine_config"]
    assert isinstance(ec, EngineConfig)
    want = dict(zip(eargs[::2], eargs[1::2]))
    assert ec.attention_impl == want["--attention-impl"]
    assert ec.mesh_shape == tuple(
        int(x) for x in want["--mesh"].split(","))
    assert ec.num_blocks == int(want["--num-blocks"])
    assert ec.block_size == int(want["--block-size"])
