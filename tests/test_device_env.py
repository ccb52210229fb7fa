"""Nothing hides the device: compile-cache placement, chip visibility, the
explicit interpret choice, and the native library's provenance."""

import os
import types

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.parallel import layout
from dynamo_tpu.utils import device_env


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_cache_dir_from_env_sets_nothing_in_code(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert device_env.configure_compile_cache() == "/x"
    assert config_updates == []
    stats = device_env.compile_cache_stats()
    assert stats["dir"] == "/x" and stats["from_env"] is True


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert device_env.configure_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]
    # fixed: the path is part of the cache key, so no pid/clock/tempdir
    assert device_env.default_cache_dir() == want


def test_cache_counters_follow_jax_monitoring(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    device_env.configure_compile_cache()
    before = device_env.compile_cache_stats()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    after = device_env.compile_cache_stats()
    assert after["hits"] - before["hits"] == 1
    assert after["misses"] - before["misses"] == 2


def test_one_chip_env_is_the_established_triple():
    assert device_env.one_chip_env(2) == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def _fake_devices(n, platform):
    return [types.SimpleNamespace(id=i, platform=platform) for i in range(n)]


def test_mesh_refuses_a_slice_of_the_visible_chips(monkeypatch):
    # four chips visible, a (1,1) mesh, no devices given: every engine built
    # this way would sit on chip 0 — an error, not a default
    monkeypatch.setattr(jax, "devices", lambda: _fake_devices(4, "tpu"))
    with pytest.raises(ValueError, match="one_chip_env"):
        layout._mesh_devices(1)
    # all of them, or an explicit choice, is fine
    assert len(layout._mesh_devices(4)) == 4
    picked = layout._mesh_devices(1, devices=jax.devices()[2:3])
    assert picked[0].id == 2
    with pytest.raises(ValueError, match="sees 4"):
        layout._mesh_devices(8)


def test_mesh_slices_virtual_cpu_devices_freely(cpu_devices):
    mesh = layout.make_mesh((1, 2))
    assert [d.id for d in mesh.devices.flat] == [0, 1]


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_interpret_is_chosen_by_the_mesh_platform(platform, want):
    mesh = types.SimpleNamespace(
        devices=np.array(_fake_devices(1, platform), dtype=object))
    assert M.pallas_interpret(mesh) is want


def test_no_pallas_path_on_other_platforms():
    mesh = types.SimpleNamespace(
        devices=np.array(_fake_devices(1, "gpu"), dtype=object))
    with pytest.raises(RuntimeError, match="gpu"):
        M.pallas_interpret(mesh)


def test_traced_attention_is_exposed(cpu_devices):
    cfg = ModelConfig.tiny()
    eng = EngineConfig(num_blocks=16, max_model_len=64, max_num_seqs=8,
                       decode_buckets=(8,), prefill_buckets=(16,))
    mesh = M.make_mesh((1, 1), cpu_devices[:1])
    params = M.init_params_sharded(jax.random.PRNGKey(0), cfg, mesh)
    cache = M.init_cache_sharded(cfg, eng, mesh)
    M.ATTENTION_TRACES.clear()
    tables = np.zeros((2, eng.max_blocks_per_seq), np.int32)
    tables[:, 0] = (1, 2)
    for T in (1, 16):  # decode → pallas (interpreted here), prefill → einsum
        tok = np.ones((2, T), np.int32)
        pos = np.tile(np.arange(T, dtype=np.int32), (2, 1))
        jax.jit(lambda p, c, t, ps, tb: M.forward(
            cfg, eng, p, c, t, ps, tb, mesh=mesh)[1]).lower(
                params, cache, tok, pos, tables)
    # the tile as traced: the kernel's default resolved for these shapes
    # (block 16: 16 pages a step of the KV walk)
    assert M.ATTENTION_TRACES["decode"] == {
        "impl": "pallas", "interpret": True, "tile": [1, 256]}
    assert M.ATTENTION_TRACES["prefill"]["impl"] == "einsum"


def test_sharded_init_equals_eager_init(cpu_devices):
    # born under the serving layout, same values as the unsharded tree
    cfg = ModelConfig.tiny()
    mesh = M.make_mesh((1, 4), cpu_devices[:4])
    sharded = M.init_params_sharded(jax.random.PRNGKey(3), cfg, mesh)
    eager = M.init_params(jax.random.PRNGKey(3), cfg)
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(eager)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    wq = sharded["layers"]["wq"]
    assert len({s.device.id for s in wq.addressable_shards}) == 4
    assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 4


def test_native_binary_is_rebuilt_when_older_than_its_source(
        tmp_path, monkeypatch):
    from dynamo_tpu import native

    src, so = tmp_path / "x.cpp", tmp_path / "x.so"
    monkeypatch.setattr(native, "_SRC_PATH", str(src))
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    src.write_text("//")
    assert native._stale()                 # no binary yet
    so.write_text("")
    os.utime(so, (1, 1))
    assert native._stale()                 # binary older than source
    os.utime(src, (0, 0))
    assert not native._stale()
    assert native.implementation() in ("native", "python")
