"""The chip's compiler, asked without a chip: the ragged paged-attention
kernel at Llama-3.2-1B widths in every shape class the engine can select,
and at the benchmark cells' own widths (Mistral-7B) in the decode class; and
whole step programs at the cells' shapes (2 layers), which must hold no copy
of a cache layer, no scatter with more updates than pages touched, no
relayout of a weight and, on four chips, no collective but the layer's two.

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

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.ops.paged_attention import paged_attention_ragged
from dynamo_tpu.parallel import layout

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
def test_prefill_grid_tiles_compile(one_chip, no_compile_cache, q_tile):
    # q tiles of the parity gate's grid at T 256
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


@pytest.mark.parametrize("kv_tile", [128, 512])
def test_mistral_decode_grid_tiles_compile(
        one_chip, no_compile_cache, kv_tile):
    # the parity gate's grid: half and twice the default's 16 pages a step
    from dynamo_tpu.ops.paged_attention import (
        VMEM_LIMIT_BYTES, default_kv_tile,
    )
    assert default_kv_tile(16, 8, 128, jnp.bfloat16) == 256
    assert "tpu_custom_call" in _compile(
        one_chip, 64, 1, hd=128, kv_tile=kv_tile)
    # two slots of K and V at the largest tile of the grid, in bf16
    assert 2 * 2 * 512 * 8 * 128 * 2 < VMEM_LIMIT_BYTES // 8


@pytest.mark.parametrize("B", [8, 128])
def test_the_seat_recurrence_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, B):
    """``delta_rule.kda_step_seats`` at ling-3.0-flash-ep8's widths (32
    heads of 128, 129 seats): a row's state is 2 MB of VMEM in and out, and
    the row's vectors become columns by one 128 x 128 transpose."""
    from dynamo_tpu.ops.delta_rule import kda_step_seats

    H, d, seats = 32, 128, 128

    def S(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = jax.jit(kda_step_seats).lower(
        S((seats + 1, H, d, d)), S((B,), jnp.int32), S((B,), jnp.bool_),
        S((B, H, d)), S((B, H, d)), S((B, H, d)), S((B, H, d)), S((B, H)),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("B,H,blocks", [(128, 32, 61440), (64, 64, 16384)])
def test_the_latent_decode_walk_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, B, H, blocks):
    """The paged decode kernel over a page that is key and value at once
    (``v_width``): the absorbed query heads over one latent "KV head" 640
    wide, at ling-3.0-flash-ep8's shape (32 heads, 128 rows, 61440 blocks)
    and longcat-flash-omni-ep32's (64 heads, 64 rows, 16384 blocks)."""
    from dynamo_tpu.ops.paged_attention import (
        default_kv_tile, paged_attention_decode,
    )

    wide, bs = 640, ENGINE.block_size

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def launch(q, plane, tables, lens):
        return paged_attention_decode(
            q, plane, plane, tables, lens, block_size=bs,
            kv_tile=default_kv_tile(bs, 1, wide, jnp.bfloat16),
            interpret=False, v_width=512, scale=192 ** -0.5)

    text = jax.jit(launch).lower(
        S((B, H, wide), jnp.bfloat16), S((blocks, 1, bs, wide), jnp.bfloat16),
        S((B, 512), jnp.int32), S((B,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("T,S,H", [(512, 2048, 64), (16, 4096, 64),
                                   (512, 8192, 32), (16, 512, 32)])
def test_the_latent_chunk_kernel_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, T, S, H):
    """A prefill chunk's tiled latent attention (PR 54) at the largest and
    smallest bucket of longcat-flash-omni-ep32 (64 heads, tables up to 4096
    keys) and ling-3.0-flash-ep8 (32 heads, 512 to 8192): one custom call,
    and no value of heads x T x S elements in the compiled program."""
    from dynamo_tpu.ops.latent_chunk_attention import (
        chunk_tiles, latent_chunk_attention,
    )

    def SD(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = latent_chunk_attention.lower(
        SD((1, T, H, 128)), SD((1, T, H, 64)), SD((1, S, 640)),
        SD((512, H * 256)), SD((1, T), jnp.int32), rank=512, rope=64,
        scale=192 ** -0.5, tiles=chunk_tiles(T, S), interpret=False).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < H * T * S


@pytest.mark.parametrize("B", [8, 128])
def test_the_gated_delta_seat_kernel_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, B):
    """``gated_delta.gdn_step_seats`` at olmo-hybrid-7b-l8's widths (30
    heads of 96 x 192, 97 seats): the pool lays two heads side by side,
    ``[97, 15, 96, 384]``, whole tiles; a row's state is 2.2 MB of VMEM in
    and out and nothing is transposed in the kernel.  Beside it the paged
    decode kernel at that table's full layers: 30 KV heads with ONE query
    head each."""
    from dynamo_tpu.ops.gated_delta import gdn_step_seats, pool_shape
    from dynamo_tpu.ops.paged_attention import (
        default_kv_tile, paged_attention_decode,
    )

    H, dk, dv, seats, bs = 30, 96, 192, 96, ENGINE.block_size

    def S(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert pool_shape(seats + 1, H, dk, dv) == (97, 15, 96, 384)
    text = jax.jit(gdn_step_seats).lower(
        S(pool_shape(seats + 1, H, dk, dv)), S((B,), jnp.int32),
        S((B,), jnp.bool_), S((B, H, dk)), S((B, H, dk)), S((B, H, dv)),
        S((B, H)), S((B, H))).compile().as_text()
    assert text.count("tpu_custom_call") == 1

    def launch(q, k, v, tables, lens):
        return paged_attention_decode(
            q, k, v, tables, lens, block_size=bs,
            kv_tile=default_kv_tile(bs, H, 128, jnp.bfloat16),
            interpret=False)

    page = S((12544, H, bs, 128), jnp.bfloat16)
    text = jax.jit(launch).lower(
        S((B, H, 128), jnp.bfloat16), page, page, S((B, 256), jnp.int32),
        S((B,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("B,pool,rows", [
    (8, jnp.bfloat16, jnp.bfloat16), (128, jnp.bfloat16, jnp.bfloat16),
    (128, jnp.float32, jnp.float32)])
def test_the_conv_step_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, B, pool, rows):
    """``gated_delta.conv_step_seats`` (PR 55) at olmo-hybrid-7b-l8's widths
    (97 seats of 3 x 11520): one custom call, and NO copy of the pool, of
    the rows' inputs or of the result around it.  The device keeps the pool
    with its seats on the sublanes, and the kernel takes it as it lies (a
    kernel over one seat's block a step has it relaid twice a call).  In
    float32, what a float32 model gives it: its 0 / 1 products take every
    pass."""
    from dynamo_tpu.ops.gated_delta import conv_step_seats

    C, seats = 11520, 96

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = jax.jit(conv_step_seats, donate_argnums=0).lower(
        S((seats + 1, 3, C), pool), S((B,), jnp.int32), S((B,), jnp.bool_),
        S((B,), jnp.bool_), S((B, C), rows), S((4, C), rows)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    wide = [line for line in text.splitlines()
            if " copy(" in line and f"{C}]" in line.split(" copy(")[0]]
    assert not wide, wide


@pytest.mark.parametrize("program", ["decode_window_b128", "prefill_T512"])
def test_the_gated_delta_table_compiles_whole_at_its_real_size(
        topo, one_chip, no_compile_cache, monkeypatch, program, capsys):
    """olmo-hybrid-7b-l8 as the cell builds it (8 layers at the published
    widths, 12544 blocks, 96 seats): the decode window of the bucket that
    serves 96 rows (128: the ladder past 64 doubles) and the T = 512 chunk
    at the widest table, compiled for a described v5e.  Three kernels are
    in the decode program (6 conv steps, 6 seat recurrences, 2 paged
    walks), no loop (``short_conv``'s slice a row was one of 128 trips a
    layer: PR 55) and no copy of a conv pool; the arguments are what the
    cell keeps resident, ~12.4 GB, and the program's own temporaries must
    fit beside them in 15.75 GB."""
    import json

    from benchmarks.chip import worker_launch as WL

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "chip", "configs",
            "olmo-hybrid-7b-l8.json")) as f:
        file = json.load(f)
    cfg = WL.model_config_from(file, False)
    args = dict(zip(file["engine_args"][::2], file["engine_args"][1::2]))
    eng = EngineConfig(
        block_size=int(args["--block-size"]),
        num_blocks=int(args["--num-blocks"]),
        max_num_seqs=int(args["--max-num-seqs"]),
        max_num_batched_tokens=int(args["--max-batched-tokens"]),
        max_model_len=int(args["--max-model-len"]))
    assert eng.decode_buckets[-1] == 128
    # the CPU backend would have the kernels interpreted
    monkeypatch.setattr(M, "pallas_interpret", lambda mesh: False)

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = place(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: M.init_cache(cfg, eng)))
    Wcap = eng.max_blocks_per_seq
    if program == "decode_window_b128":
        ctl = place(jax.eval_shape(
            lambda: M.init_ctl(eng, eng.max_num_seqs, Wcap)))
        window, _ = M.make_autopilot_fns(cfg, eng, Wcap, None)
        compiled = window.__wrapped__.lower(
            params, cache, ctl, S((128,), jnp.int32)).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 14
        assert " while(" not in text
        assert not [line for line in text.splitlines()
                    if " copy(" in line and "[97,3,11520]" in line]
    else:
        T, W = 512, Wcap
        fn = M.make_packed_prefill_fn(cfg, eng, T, W, None)
        compiled = fn.__wrapped__.lower(
            params, cache, S((eng.max_num_seqs + 1,), jnp.int32),
            S((1, T + W + M.PP_SCALARS), jnp.int32),
            S((2,), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nolmo-hybrid-7b-l8 {program}: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert 12.0e9 < mem.argument_size_in_bytes < 12.8e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75e9)


@pytest.mark.parametrize("phase", ["decode", "prefill"])
@pytest.mark.parametrize("D,F,E,Eh,k,seqs,router", [
    (3072, 1024, 256, 128, 10, 32, {}),
    (2560, 768, 512, 64, 8, 128,
     dict(score="sigmoid", n_group=8, topk_group=4)),
    (6144, 2048, 768, 16, 12, 64, dict(n_zero=256, renormalise=False)),
])
def test_the_expert_layer_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, D, F, E, Eh, k, seqs, router, phase):
    """``moe.routed_ffn`` at laguna-s-2.1-ep2's, ling-3.0-flash-ep8's and
    longcat-flash-omni-ep32's widths (the last: a router of 512 + 256
    zero-compute outputs over 16 held experts), a decode window's rows and
    a T=512 chunk's: the tiles
    ``gmm_tile`` returns there (tk = K: a block of a whole gate matrix)
    fit the scoped VMEM as Mosaic counts it, and the layer is three grouped
    matmuls."""
    from dynamo_tpu.parallel.moe import routed_ffn

    rows = seqs if phase == "decode" else 512

    def S(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def layer(x, wr, wg, wu, wd):
        return routed_ffn(x, wr, wg, wu, wd, top_k=k, held_start=0,
                          **router)[0]

    text = jax.jit(layer).lower(
        S((rows, D)), S((D, E), jnp.float32), S((Eh, D, F)), S((Eh, D, F)),
        S((Eh, F, D))).compile().as_text()
    assert text.count("tpu_custom_call") == 3


# ---- whole step programs at the cells' shapes: the cache keeps its layout ---
#
# ``forward`` writes K and V whole pages at a time, by a scatter whose indexed
# dim leads and whose window is the page (``model._kv_write``). The plain
# ``.at[block, :, off].set`` made XLA's layout assignment ask for the cache
# as {3,1,2,0} and copy every layer's K and V into that layout and back, a
# step: half of every step program on the chip (PERF.md, PR 29); PR 29's own
# cure, one update per (token, head), cost ~70 ns an update: 4.4 ms of a
# 16.3 ms T=256 chunk (PERF.md, PR 33). Compile only: Mistral-7B widths, 2
# layers, the benchmark's engine arguments.

CELL_MODEL = dict(
    vocab_size=32768, hidden_size=4096, intermediate_size=14336,
    num_heads=32, num_kv_heads=8, head_dim=128, rope_theta=1e6,
    rms_norm_eps=1e-5, tie_word_embeddings=False, max_position=32768)
CELL_LAYERS = 2


def _cell_programs(topo, mesh_shape):
    """(cfg, eng, mesh, params, cache, S): the cells' model at 2 layers as
    shapes placed on the described chips; ``S(shape, dtype)`` is a
    replicated argument."""
    cfg = ModelConfig(num_layers=CELL_LAYERS, **CELL_MODEL)
    eng = EngineConfig(mesh_shape=mesh_shape)   # block 16, 2048 blocks, pallas
    n = mesh_shape[0] * mesh_shape[1]
    mesh = layout.make_mesh(mesh_shape, devices=topo.devices[:n])
    params = jax.eval_shape(
        lambda: M._init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, eng))
    if n > 1:
        repl = layout.replicated(mesh)
        p_sh = M.param_shardings(mesh, cfg)
        c_sh = M.cache_shardings(mesh, cfg)
    else:
        repl = SingleDeviceSharding(topo.devices[0])
        p_sh = jax.tree.map(lambda _: repl, params)
        c_sh = jax.tree.map(lambda _: repl, cache)

    def place(tree, sh):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sh)

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=repl)

    return cfg, eng, mesh, place(params, p_sh), place(cache, c_sh), S


def _decode_window_text(topo):
    cfg, eng, mesh, params, cache, S = _cell_programs(topo, (1, 1))
    Wcap = eng.max_blocks_per_seq
    ctl = jax.tree.map(lambda a: S(a.shape, a.dtype),
                       M.init_ctl(eng, eng.max_num_seqs, Wcap))
    window, _ = M.make_autopilot_fns(cfg, eng, Wcap, mesh)
    return window.__wrapped__.lower(
        params, cache, ctl, S((64,), jnp.int32)).compile().as_text()


def _prefill_text(topo, T=256, W=16):
    cfg, eng, mesh, params, cache, S = _cell_programs(topo, (1, 1))
    fn = M.make_packed_prefill_fn(cfg, eng, T, W, mesh)
    return fn.__wrapped__.lower(
        params, cache, S((eng.max_num_seqs + 1,), jnp.int32),
        S((1, T + W + M.PP_SCALARS), jnp.int32),
        S((2,), jnp.uint32)).compile().as_text()


def _tp4_forward_text(topo):
    cfg, eng, mesh, params, cache, S = _cell_programs(topo, (1, 4))
    fwd = jax.jit(
        lambda p, c, t, pos, bt: M.forward(cfg, eng, p, c, t, pos, bt,
                                           mesh=mesh),
        donate_argnums=(1,),
        **M._io_kwargs(mesh, cfg, 3, ("cache", "repl"), eng=eng))
    return fwd.lower(
        params, cache, S((64, 1), jnp.int32), S((64, 1), jnp.int32),
        S((64, eng.max_blocks_per_seq), jnp.int32)).compile().as_text()


def _cache_sized_copies(text, kv_heads=CELL_MODEL["num_kv_heads"]):
    """``copy`` / ``copy-start`` ops whose result holds as many elements as
    one cache layer (on a device: ``kv_heads`` of them) — the layer or any
    view of it."""
    hd = CELL_MODEL["head_dim"]
    layer = ENGINE.num_blocks * kv_heads * ENGINE.block_size * hd
    found = []
    for line in text.splitlines():
        m = re.search(r" = (.*?) copy(-start)?\(", line)
        if m is None:
            continue
        for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
            dims = [int(d) for d in dims.split(",")]
            # hd is never merged into another dim: that tells a view of the
            # cache from a weight of as many elements
            if math.prod(dims) == layer and dims[-1] == hd:
                found.append(line.strip()[:120])
                break
    return found


def _collectives(text):
    return {k: len(re.findall(r" = \S+ %s(?:-start)?\(" % k, text))
            for k in ("all-reduce", "all-gather", "all-to-all",
                      "collective-permute", "reduce-scatter")}


@pytest.mark.parametrize("program", ["decode_window_b64", "prefill_T256"])
def test_cell_step_programs_keep_the_cache_layout(
        topo, no_compile_cache, program):
    text = (_decode_window_text(topo) if program == "decode_window_b64"
            else _prefill_text(topo))
    assert "bf16[2048,8,16,128]" in text          # the cache is in there
    assert _cache_sized_copies(text) == []
    if program == "decode_window_b64":
        assert text.count("tpu_custom_call") >= CELL_LAYERS


def test_the_plain_scatter_is_what_copies_the_cache(
        topo, no_compile_cache, monkeypatch):
    # the reader above sees the copies where they are: the old expression
    # brings K in, K out, V in, V out per layer
    monkeypatch.setattr(M, "_kv_write", _plain_write)
    assert len(_cache_sized_copies(_prefill_text(topo))) >= 4 * CELL_LAYERS


def _rows_of_plan(pages, shift, mask, upd):
    """(block [N], slot [N], row [N, KV, ...]) of every slot of a page plan
    (``model._kv_pages``): slot ``j`` of a row's pages takes the row's token
    ``j - shift``; a slot that takes none points at block 0."""
    T = upd.shape[1]
    bs = mask.shape[1] // pages.shape[1]
    slot = jnp.broadcast_to(jnp.arange(mask.shape[1])[None, :], mask.shape)
    blocks = jnp.where(mask, jnp.repeat(pages, bs, axis=1), 0)
    tok = jnp.clip(slot - shift[:, None], 0, T - 1)
    rows = jnp.take_along_axis(
        upd, tok.reshape(tok.shape + (1,) * (upd.ndim - 2)), axis=1)
    return (blocks.reshape(-1), (slot % bs).reshape(-1),
            rows.reshape((-1,) + upd.shape[2:]))


def _plain_write(plane, pages, shift, mask, upd, mesh=None):
    blocks, offs, rows = _rows_of_plan(pages, shift, mask, upd)
    return plane.at[blocks, :, offs].set(rows)


def _row_write(plane, pages, shift, mask, upd, mesh=None):
    """PR 29's form: one update per (token, head) through the free
    ``[NB*KV*bs, hd]`` view."""
    blocks, offs, rows = _rows_of_plan(pages, shift, mask, upd)
    NB, KV, bs = plane.shape[:3]
    at = (blocks[:, None] * KV + jnp.arange(KV)[None, :]) * bs + offs[:, None]
    view = plane.reshape((NB * KV * bs,) + plane.shape[3:])
    return view.at[at.reshape(-1)].set(
        rows.reshape((-1,) + rows.shape[2:])).reshape(plane.shape)


def _scatter_updates(text):
    """Updates each ``scatter`` of the compiled program carries: the
    product of its updates operand's dims outside the update window."""
    counts = []
    shapes = dict(re.findall(r"%?(\S+) = \S+?\[([\d,]*)\]", text))
    for m in re.finditer(
            r" scatter\((?:\S+ )?%?\S+, (?:\S+ )?%?\S+, (?:\S+ )?%?(\S+?)\)"
            r", update_window_dims=\{([\d,]*)\}", text):
        dims = [int(d) for d in shapes[m.group(1)].split(",") if d]
        window = {int(d) for d in m.group(2).split(",") if d}
        counts.append(math.prod(
            d for i, d in enumerate(dims) if i not in window))
    return counts


def test_prefill_scatters_carry_no_more_updates_than_pages(
        topo, no_compile_cache, monkeypatch):
    # B x P updates a plane: 17 pages at T=256, block 16. The row form made
    # 256 x 8 = 2048, and would be seen here
    T, bs = 256, ENGINE.block_size
    pages = (T + bs - 2) // bs + 1
    counts = _scatter_updates(_prefill_text(topo, T=T))
    cache_writes = [c for c in counts if c > 1]
    assert len(cache_writes) >= 2 * CELL_LAYERS       # K and V a layer
    assert max(counts) <= pages == 17
    # the reader sees the row form where it is: one update a (token, head)
    monkeypatch.setattr(M, "_kv_write", _row_write)
    text = _prefill_text(topo, T=T)
    assert _cache_sized_copies(text) == []
    assert max(_scatter_updates(text)) >= T * CELL_MODEL["num_kv_heads"]


def test_tp4_decode_keeps_the_cache_layout_and_adds_no_collective(
        topo, no_compile_cache):
    text = _tp4_forward_text(topo)
    assert "bf16[2048,2,16,128]" in text          # a shard of the cache
    assert _cache_sized_copies(text, kv_heads=2) == []
    assert _cache_sized_copies(text, kv_heads=8) == []
    # o_proj and down_proj a layer, and the vocabulary-sharded embedding
    assert _collectives(text) == {
        "all-reduce": 2 * CELL_LAYERS + 1, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0, "reduce-scatter": 0}
    assert text.count("tpu_custom_call") >= CELL_LAYERS


# ---- no step program moves a weight before it multiplies by it --------------
#
# A parameter's layout is fixed before the program is compiled. Where XLA
# folds the head reshape into the q and k projections it wants ``wq`` / ``wk``
# with ``D`` minor and transposes every layer's slice of both inside the step
# (a ``slice_bitcast_fusion`` that writes the slices, a ``copy`` a layer that
# re-lays them: 3.4 of a 16.5 ms decode step, PERF.md PR 31). The barrier in
# ``model._qkv_proj`` keeps the products plain matmuls; the compiler then
# asks for no layout but the default one.

_PROGRAMS = {"decode_window_b64": (_decode_window_text, (1, 1)),
             "prefill_T256": (_prefill_text, (1, 1)),
             "tp4_forward": (_tp4_forward_text, (1, 4))}


def _weight_relayouts(text, tp):
    """``copy`` ops and ``slice_bitcast_fusion`` loop fusions whose result
    holds an array of 2 MB or more with the last two dims of a layer weight
    (on a device: of its shard).  Asynchronous ``copy-start`` prefetches,
    which overlap the matmuls, are not among them."""
    D, F = CELL_MODEL["hidden_size"], CELL_MODEL["intermediate_size"]
    hd = CELL_MODEL["head_dim"]
    outs = {CELL_MODEL["num_heads"] * hd // tp,
            CELL_MODEL["num_kv_heads"] * hd // tp, F // tp}
    found = []
    for line in text.splitlines():
        m = re.search(r"^\s*(?:ROOT )?%?(\S+) = (.*?) (copy|fusion)\(", line)
        if m is None or (m.group(3) == "fusion"
                         and "slice_bitcast_fusion" not in m.group(1)):
            continue
        for dims in re.findall(r"bf16\[([\d,]+)\]", m.group(2)):
            dims = [int(d) for d in dims.split(",")]
            if (len(dims) >= 2 and 2 * math.prod(dims) >= 2 << 20
                    and D in dims[-2:] and set(dims[-2:]) - {D} <= outs):
                found.append(line.strip()[:120])
                break
    return found


def _asked_layouts(topo, mesh_shape):
    """What the compiled decode window would have each stacked matrix lie
    as, left free to choose (``Layout.AUTO``): leaf -> ``major_to_minor``
    where that is not the default order."""
    cfg, eng, mesh, params, cache, S = _cell_programs(topo, mesh_shape)
    ask = jax.tree.map(
        lambda a: Format(Layout.AUTO, a.sharding) if a.ndim == 3
        else a.sharding, params)
    bare = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        params)
    Wcap = eng.max_blocks_per_seq
    ctl = jax.tree.map(lambda a: S(a.shape, a.dtype),
                       M.init_ctl(eng, eng.max_num_seqs, Wcap))
    kw = M._io_kwargs(mesh, cfg, 2, ("cache", "repl", "repl"), eng=eng)
    rest = kw.pop("in_shardings", (None,) * 4)[1:]
    compiled = jax.jit(
        M.raw_autopilot_window_fn(cfg, eng, mesh), donate_argnums=(1, 2),
        in_shardings=(ask,) + tuple(rest), **kw,
    ).lower(bare, cache, ctl, S((64,), jnp.int32)).compile()
    return {name: tuple(fmt.layout.major_to_minor)
            for name, fmt in compiled.input_formats[0][0]["layers"].items()
            if tuple(fmt.layout.major_to_minor)
            != tuple(range(len(fmt.layout.major_to_minor)))}


def _without_the_barrier(monkeypatch):
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)


@pytest.mark.parametrize("program", sorted(_PROGRAMS))
def test_cell_step_programs_move_no_weight(topo, no_compile_cache, program):
    text_of, mesh_shape = _PROGRAMS[program]
    text = text_of(topo)
    assert "bf16[%d,4096,%d]" % (CELL_LAYERS, 4096 // mesh_shape[1]) in text
    assert _weight_relayouts(text, mesh_shape[1]) == []


@pytest.mark.parametrize("program", sorted(_PROGRAMS))
def test_the_folded_reshape_is_what_moves_the_weights(
        topo, no_compile_cache, monkeypatch, program):
    # the reader above sees the relayouts where they are: without the
    # barrier wq and wk (on four chips a shard of wv too) are sliced by a
    # fusion each and transposed once a layer
    _without_the_barrier(monkeypatch)
    text_of, mesh_shape = _PROGRAMS[program]
    assert len(_weight_relayouts(text_of(topo), mesh_shape[1])) \
        >= 2 * CELL_LAYERS


@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4)])
def test_the_compiler_asks_for_no_layout_but_the_default(
        topo, no_compile_cache, monkeypatch, mesh_shape):
    # left free to choose, the decode window takes every stacked matrix as
    # it is stored; without the barrier it would have the projections that
    # feed rope with D minor, per leaf and per mesh
    assert _asked_layouts(topo, mesh_shape) == {}
    _without_the_barrier(monkeypatch)
    asked = _asked_layouts(topo, mesh_shape)
    assert asked["wq"] == asked["wk"] == (0, 2, 1)
    assert set(asked) <= {"wq", "wk", "wv"}
