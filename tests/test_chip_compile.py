"""The chip's compiler, asked without a chip: the ragged paged-attention
kernel at Llama-3.2-1B widths in every shape class the engine can select,
and at the benchmark cells' own widths (Mistral-7B) in the decode class.

Interpret-mode parity (every other kernel test) cannot see what Mosaic
refuses — a block that overflows scoped VMEM, a slice off the dtype's tile.
These compile the real kernel (``interpret=False``) for a *described* v5e
(``jax.experimental.topologies``; no device attached, nothing runs), about
two seconds each, so every later PR is held to "the chip's compiler accepts
the main path's kernels" at no chip time.

Rules this file keeps (see the on-chip-measurement guide): the topology is
described inside a module-scoped, non-autouse fixture that skips when it
cannot be; nothing touches ``topologies`` at import, in ``skipif``, in
``parametrize`` or in ``conftest.py``; all such tests live in this one file
(only one process may load the TPU library, and under xdist ``--dist
loadfile`` one file is one worker); the persistent compilation cache is off
around them (a program compiled for a described device cannot be read back).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.ops.paged_attention import paged_attention_ragged

pytestmark = pytest.mark.chipcompile

MODEL = ModelConfig.llama3_1b()     # H 32, KV 8, hd 64, bf16
ENGINE = EngineConfig()             # block 16, 2048 blocks, 8k context
SPEC_T = 4 + 1                      # spec_k + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(one_chip, B, T, *, kv_dtype=jnp.bfloat16, quantized=False,
             q_tile=0, kv_tile=0, H=MODEL.num_heads, KV=MODEL.num_kv_heads,
             hd=MODEL.head_dim_):
    """Compile one launch for the described chip; returns the HLO text."""
    bs = ENGINE.block_size
    NB, W = ENGINE.num_blocks, ENGINE.max_blocks_per_seq

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [
        S((B * T, H, hd), jnp.bfloat16),
        S((NB, KV, bs, hd), kv_dtype), S((NB, KV, bs, hd), kv_dtype),
        S((B, W), jnp.int32), S((B + 1,), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32),
    ]
    kw = {}
    if quantized:
        kw = {"k_scale": S((NB, KV, bs), jnp.float32),
              "v_scale": S((NB, KV, bs), jnp.float32)}

    def launch(*a, **k):
        return paged_attention_ragged(
            *a, block_size=bs, max_q_len=T,
            q_tile=1 if T == 1 else q_tile, kv_tile=kv_tile,
            interpret=False, **k)

    return jax.jit(launch).lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("B", ENGINE.decode_buckets)
def test_decode_compiles_at_every_bucket(one_chip, no_compile_cache, B):
    assert "tpu_custom_call" in _compile(one_chip, B, 1)


def test_prefill_compiles_at_largest_bucket(one_chip, no_compile_cache):
    # T 512 → default q_tile 128: refused for scoped VMEM (17.4 MB against
    # the 16 MB default) until the kernel passed an explicit limit
    T = max(ENGINE.prefill_buckets)
    assert "tpu_custom_call" in _compile(one_chip, 1, T)
    assert "tpu_custom_call" in _compile(one_chip, 4, 256)


def test_spec_window_compiles(one_chip, no_compile_cache):
    assert "tpu_custom_call" in _compile(one_chip, 8, SPEC_T)


@pytest.mark.parametrize("name,dtype", [
    ("int8", jnp.int8), ("fp8", jnp.float8_e4m3fn)])
def test_quantized_kv_decode_and_prefill_compile(
        one_chip, no_compile_cache, name, dtype):
    T = max(ENGINE.prefill_buckets)
    assert "tpu_custom_call" in _compile(
        one_chip, 8, 1, kv_dtype=dtype, quantized=True)
    assert "tpu_custom_call" in _compile(
        one_chip, 1, T, kv_dtype=dtype, quantized=True)


def test_tp4_shard_of_the_kernel_compiles(one_chip, no_compile_cache):
    # what each device runs under shard_map at --mesh 1,4: a quarter of the
    # heads (H 8, KV 2), decode and prefill
    assert "tpu_custom_call" in _compile(one_chip, 8, 1, H=8, KV=2)
    assert "tpu_custom_call" in _compile(
        one_chip, 1, max(ENGINE.prefill_buckets), H=8, KV=2)


@pytest.mark.parametrize("q_tile", [1, 8, 64])
def test_prefill_sweep_tiles_compile(one_chip, no_compile_cache, q_tile):
    # engine.autotune sweeps these at T 256 and raises on a refusal
    assert "tpu_custom_call" in _compile(one_chip, 4, 256, q_tile=q_tile)


# the benchmark's configuration (benchmarks/chip/configs/mistral-7b-v0.3-l16):
# H 32, KV 8, hd 128, bf16, block 16, table width 512; KV 2 is what one
# device runs at --mesh 1,4
@pytest.mark.parametrize("B", [8, 64])
@pytest.mark.parametrize("H,KV", [(32, 8), (8, 2)])
def test_mistral_decode_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, B, H, KV):
    assert ENGINE.max_blocks_per_seq == 512
    text = _compile(one_chip, B, 1, H=H, KV=KV, hd=128)
    assert "tpu_custom_call" in text
    # the kernel declares its scoped-VMEM limit and stays a single call
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("kv_tile", [64, 256])
def test_mistral_decode_sweep_tiles_compile(
        one_chip, no_compile_cache, kv_tile):
    # engine.autotune offers half and twice the default's 8 pages a step
    from dynamo_tpu.ops.paged_attention import (
        VMEM_LIMIT_BYTES, default_kv_tile,
    )
    assert default_kv_tile(16, 8, 128, jnp.bfloat16) == 128
    assert "tpu_custom_call" in _compile(
        one_chip, 64, 1, hd=128, kv_tile=kv_tile)
    # two slots of K and V at the largest offered tile, in bf16
    assert 2 * 2 * 256 * 8 * 128 * 2 < VMEM_LIMIT_BYTES // 8
