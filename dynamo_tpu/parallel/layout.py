"""Canonical sharding layout — the ONE place axis names, meshes, and
PartitionSpecs come from.

Every sharded tensor in the system agrees on this vocabulary (SNIPPETS.md
[3]: a ``SpecLayout``-style single source of truth); MULTICHIP_r05's
involuntary-rematerialization storm came from modules free-handing their
own axis strings, mesh shapes, and per-call-site ``PartitionSpec``
literals.  dynalint rules DT501/DT502/DT503 enforce that axis-name
literals, ``Mesh`` construction, and axis-carrying ``PartitionSpec``
construction live here and nowhere else — new layouts are added by
extending this module, not by spelling ``P(None, "tp")`` at a call site.

Axes:

- ``dp``   data parallel — independent batch shards
- ``tp``   tensor parallel — attention/MLP heads split per chip
- ``sp``   sequence parallel — ring attention over long prompts
- ``ep``   expert parallel — MoE experts spread over chips
- ``pp``   pipeline parallel — layer stages
- ``fsdp`` fully-sharded data parallel (parameter storage sharding)

The serving engine's meshes are ``(dp, tp)`` (2D) or ``(dp, fsdp, tp)``
(3D).  Sequence-parallel ring prefill runs over the SAME serving mesh with
the sequence axis sharded over the composite ``(dp, tp)`` (optionally
``(dp, fsdp, tp)``) axes — NOT over a separate flat ``sp`` mesh.  Two
meshes over one device set is exactly what produced the
``{devices=[8,1,1]} -> {devices=[1,4,1,2]}`` reshape storms: GSPMD cannot
translate shardings between meshes and falls back to full
rematerialization on every tensor crossing the boundary.  One mesh, one
spec table, zero involuntary remats.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_EP = "ep"
AXIS_PP = "pp"
AXIS_FSDP = "fsdp"

#: every axis name the serving system may use; dynalint's DT501 vocabulary
#: mirrors this tuple (plus the legacy "data" alias it also polices).
ALL_AXES: Tuple[str, ...] = (
    AXIS_DP, AXIS_TP, AXIS_SP, AXIS_EP, AXIS_PP, AXIS_FSDP,
)

#: one PartitionSpec entry: None (replicated), an axis name, or a tuple of
#: axis names (composite sharding — e.g. the sequence axis over (dp, tp)).
SpecEntry = Union[None, str, Tuple[str, ...]]


def spec(*entries: SpecEntry) -> PartitionSpec:
    """The one validated ``PartitionSpec`` constructor.

    Entries must be ``None``, a canonical axis name, or a tuple of
    canonical axis names.  Everything outside this module builds its specs
    through here (or the :class:`SpecLayout` methods below) — dynalint
    DT503 flags direct axis-carrying ``PartitionSpec(...)`` calls.
    """
    for e in entries:
        names = e if isinstance(e, tuple) else (e,)
        for a in names:
            if a is not None and a not in ALL_AXES:
                raise ValueError(
                    f"unknown mesh axis {a!r} in spec entry {e!r}; "
                    f"canonical axes: {ALL_AXES}")
    return PartitionSpec(*entries)


def named(mesh: Mesh, *entries: SpecEntry) -> NamedSharding:
    """``NamedSharding(mesh, spec(*entries))`` — validated."""
    return NamedSharding(mesh, spec(*entries))


def replicated(mesh: Mesh) -> NamedSharding:
    """The fully-replicated sharding on ``mesh`` (control state, scalars,
    sampled token ids — everything small enough to live everywhere)."""
    return NamedSharding(mesh, spec())


# ------------------------------ shard_map ---------------------------------


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — our bodies return
    pallas_call / collective outputs that carry no replication info.  Every
    shard_map in the tree goes through here so that choice has one home."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# ------------------------------ meshes ------------------------------------


def _mesh_devices(n: int, devices=None) -> np.ndarray:
    """The ``n`` devices a mesh is built over.

    Explicit ``devices`` are taken as given (leading ``n``).  Without them
    the mesh takes the process's devices in enumeration order — which on
    real chips must be ALL of them: a process opens every chip it can see,
    so a mesh smaller than ``jax.devices()`` would leave chips held but
    idle, and a second engine built the same way would land on the same
    first chip.  Restrict what the process sees instead
    (``utils.device_env.one_chip_env`` in the launcher) or pass ``devices``.
    Virtual CPU devices are not chips; tests slice them freely."""
    if devices is not None:
        return np.asarray(devices).flatten()[:n]
    visible = jax.devices()
    if len(visible) < n:
        raise ValueError(
            f"mesh needs {n} devices, this process sees {len(visible)}")
    if len(visible) > n and visible[0].platform != "cpu":
        raise ValueError(
            f"mesh needs {n} of the {len(visible)} "
            f"{visible[0].platform} devices this process holds and no "
            f"`devices` were given: every engine built this way would "
            f"sit on device 0. Start the process with only its chips "
            f"visible (dynamo_tpu.utils.device_env.one_chip_env) or pass "
            f"devices explicitly.")
    return np.asarray(visible[:n])


def make_mesh(shape: Sequence[int], devices=None) -> Mesh:
    """The serving engine's canonical mesh: ``(dp, tp)`` for a 2-tuple,
    ``(dp, fsdp, tp)`` for a 3-tuple.

    Devices come from :func:`_mesh_devices`: enumeration order, so every
    host in a multihost slice derives the identical mesh.
    """
    shape = tuple(int(s) for s in shape)
    if len(shape) == 2:
        axes: Tuple[str, ...] = (AXIS_DP, AXIS_TP)
    elif len(shape) == 3:
        axes = (AXIS_DP, AXIS_FSDP, AXIS_TP)
    else:
        raise ValueError(
            f"mesh shape must be (dp, tp) or (dp, fsdp, tp), got {shape}")
    n = int(np.prod(shape))
    return Mesh(_mesh_devices(n, devices).reshape(shape), axes)


def make_axes_mesh(shape: Sequence[int], axis_names: Sequence[str],
                   devices=None) -> Mesh:
    """General N-D mesh over the leading ``prod(shape)`` devices; axis
    names must come from :data:`ALL_AXES`."""
    unknown = [a for a in axis_names if a not in ALL_AXES]
    if unknown:
        raise ValueError(
            f"unknown mesh axis names {unknown}; canonical axes: {ALL_AXES}")
    n = int(np.prod(shape))
    return Mesh(_mesh_devices(n, devices).reshape(tuple(shape)),
                tuple(axis_names))


# ----------------------------- SpecLayout ---------------------------------


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Frozen per-parameter PartitionSpec table over the serving mesh.

    Each field holds the mesh axis a role shards over, or ``None`` when the
    mesh doesn't carry that axis (or carries it at size 1 — sharding over a
    singleton axis is replication wearing a costume, and naming absent axes
    in a NamedSharding is an error).  Build with :meth:`for_mesh` so the
    table always matches the mesh it will be used with.

    The table (stacked scan tree, ``L`` = layers):

    ====================  ====================  =============================
    leaf                  shape                 spec
    ====================  ====================  =============================
    embed                 [V, D]                (tp, fsdp)   vocab-sharded
    layers/attn_norm      [L, D]                ()           replicated
    layers/wq             [L, D, H*hd]          (None, fsdp, tp)   column
    layers/wk, wv         [L, D, KV*hd]         (None, fsdp, tp)   column
    layers/wo             [L, H*hd, D]          (None, tp, fsdp)   row
    layers/mlp_norm       [L, D]                ()           replicated
    layers/w_gate, w_up   [L, D, F]             (None, fsdp, tp)   column
    layers/w_down         [L, F, D]             (None, tp, fsdp)   row
    layers/w_router       [L, D, E]             ()           replicated
    layers/w_gate (moe)   [L, E, D, F]          (None, ep, None, None)
    layers/w_up (moe)     [L, E, D, F]          (None, ep, None, None)
    layers/w_down (moe)   [L, E, F, D]          (None, ep, None, None)
    final_norm            [D]                   ()           replicated
    lm_head               [D, V]                (fsdp, tp)   column
    KV cache (per layer)  [NB, KV, bs, hd]      (None, tp, None, None)
    KV block transfer     [L, N, KV, bs, hd]    (None, None, tp, None, None)
    hidden states         [B, T, D]             ()    (seq path: (None, seq))
    logits                [B, V]                (None, tp)
    ====================  ====================  =============================

    Column-sharded projections contract over the replicated D axis — each
    output element is computed whole on one chip, so sharded and unsharded
    runs are bitwise identical per partial product; row-sharded projections
    meet the column outputs so the only cross-chip reduction is the one
    Megatron all-reduce per block.  The MoE expert axis rides ``ep`` when
    the mesh has one and falls back to ``tp`` (dispatch/combine become
    all-to-alls under GSPMD).
    """

    dp: Optional[str] = None
    fsdp: Optional[str] = None
    tp: Optional[str] = None
    ep: Optional[str] = None

    @staticmethod
    def for_mesh(mesh: Optional[Mesh]) -> "SpecLayout":
        """Derive the layout from a mesh, dropping absent/singleton axes."""
        if mesh is None:
            return SpecLayout()

        def have(axis: str) -> Optional[str]:
            return axis if mesh.shape.get(axis, 1) > 1 else None

        return SpecLayout(
            dp=have(AXIS_DP),
            fsdp=have(AXIS_FSDP),
            tp=have(AXIS_TP),
            ep=have(AXIS_EP) or have(AXIS_TP),
        )

    # ------------------------- sequence axis ---------------------------

    def seq_axes(self) -> SpecEntry:
        """The composite axis the ring-sp prefill shards the sequence over:
        every data-carrying serving axis — ``("dp", "tp")`` on the 2D mesh.
        Using the serving mesh's own axes (not a separate flat ``sp`` mesh
        over the same devices) is what lets GSPMD reshard ring-layout K/V
        into the head-sharded paged cache without involuntary
        rematerialization.  The order is mesh-major (dp outermost): the
        row-major composite device enumeration then equals the flat device
        enumeration, which is the convention ``axis_index``/``ppermute``
        and shard_map chunk placement all agree on — the ring's chunk->
        owner bookkeeping depends on that agreement.  The seq->heads
        handoff at the cache scatter does not constrain the order; the
        forward pass pins it as an explicit replicate-then-slice, which is
        order-independent."""
        axes = tuple(a for a in (self.dp, self.fsdp, self.tp) if a)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]

    # ----------------------- parameter specs ---------------------------

    def embed(self) -> PartitionSpec:
        return spec(self.tp, self.fsdp)

    def norm_stacked(self) -> PartitionSpec:
        return spec(None, None)

    def norm(self) -> PartitionSpec:
        return spec(None)

    def column_stacked(self) -> PartitionSpec:
        """[L, in, out] column-parallel: wq/wk/wv, dense w_gate/w_up."""
        return spec(None, self.fsdp, self.tp)

    def row_stacked(self) -> PartitionSpec:
        """[L, in, out] row-parallel: wo, dense w_down."""
        return spec(None, self.tp, self.fsdp)

    def router_stacked(self) -> PartitionSpec:
        return spec(None, None, None)

    def expert_stacked(self) -> PartitionSpec:
        """[L, E, in, out] — experts spread over ep (tp fallback)."""
        return spec(None, self.ep, None, None)

    def lm_head(self) -> PartitionSpec:
        return spec(self.fsdp, self.tp)

    @staticmethod
    def scale_spec(weight_spec: PartitionSpec) -> PartitionSpec:
        """Per-channel quantization scales ride the weight's shape with the
        contraction axis (-2) reduced to size 1 (``keepdims``), so the
        scale's spec is the weight's with that entry replicated — sharding
        a singleton dim over a real axis is indivisible."""
        entries = list(weight_spec)
        entries[-2] = None
        return spec(*entries)

    def param_specs(self, cfg, weight_dtype: str = "bf16"
                    ) -> Dict[str, Any]:
        """PartitionSpec tree matching ``model.init_params(cfg)``; with a
        quantized ``weight_dtype`` each matmul leaf becomes a
        ``{"q": weight_spec, "s": scale_spec}`` dict mirroring the
        quantized param pytree (engine/quant.py)."""
        from ..engine import quant

        def w(s: PartitionSpec, name: str) -> Any:
            if quant.is_quantized(weight_dtype) and quant.is_weight_leaf(
                    name):
                return {"q": s, "s": self.scale_spec(s)}
            return s

        layers: Dict[str, Any] = {
            "attn_norm": self.norm_stacked(),
            "wq": w(self.column_stacked(), "wq"),
            "wk": w(self.column_stacked(), "wk"),
            "wv": w(self.column_stacked(), "wv"),
            "wo": w(self.row_stacked(), "wo"),
            "mlp_norm": self.norm_stacked(),
        }
        if cfg.is_moe:
            layers["w_router"] = self.router_stacked()
            layers["w_gate"] = w(self.expert_stacked(), "w_gate")
            layers["w_up"] = w(self.expert_stacked(), "w_up")
            layers["w_down"] = w(self.expert_stacked(), "w_down")
        else:
            layers["w_gate"] = w(self.column_stacked(), "w_gate")
            layers["w_up"] = w(self.column_stacked(), "w_up")
            layers["w_down"] = w(self.row_stacked(), "w_down")
        specs: Dict[str, Any] = {
            "embed": self.embed(),
            "layers": layers,
            "final_norm": self.norm(),
        }
        if not cfg.tie_word_embeddings:
            specs["lm_head"] = w(self.lm_head(), "lm_head")
        return specs

    def param_shardings(self, mesh: Mesh, cfg,
                        weight_dtype: str = "bf16") -> Dict[str, Any]:
        return jax.tree.map(
            functools.partial(NamedSharding, mesh),
            self.param_specs(cfg, weight_dtype),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )

    # ---------------------- cache / activations ------------------------

    def cache_block(self) -> PartitionSpec:
        """One paged-cache layer [NB, KV, bs, hd]: KV heads over tp, so
        each chip holds exactly the heads it computes."""
        return spec(None, self.tp, None, None)

    def cache_scale_block(self) -> PartitionSpec:
        """Per-layer KV-scale cache [NB, KV, bs] (quantized kv_dtype):
        heads over tp, matching :meth:`cache_block` minus the hd axis."""
        return spec(None, self.tp, None)

    def cache_shardings(self, mesh: Mesh, cfg,
                        kv_dtype: str = "bf16") -> Dict[str, Any]:
        from ..engine import quant

        s = NamedSharding(mesh, self.cache_block())
        out = {"k": [s] * cfg.num_layers, "v": [s] * cfg.num_layers}
        if quant.is_quantized(kv_dtype):
            ss = NamedSharding(mesh, self.cache_scale_block())
            out["ks"] = [ss] * cfg.num_layers
            out["vs"] = [ss] * cfg.num_layers
        return out

    def kv_blocks(self) -> PartitionSpec:
        """Extracted/injected KV block payload [L, N, KV, bs, hd] — the
        disagg transfer layout; KV heads carry tp exactly like the cache,
        so a P->D handoff between equal-TP meshes never reshards."""
        return spec(None, None, self.tp, None, None)

    def kv_scale_blocks(self) -> PartitionSpec:
        """Scale payload [L, N, KV, bs] riding the block transfer when the
        cache is quantized; tp on the heads like :meth:`kv_blocks`."""
        return spec(None, None, self.tp, None)

    def hidden(self) -> PartitionSpec:
        """Dense-path activations [B, T, D]: replicated (the Megatron
        pattern — column/row sharded weights keep per-chip activations
        whole; only heads are ever sharded mid-block)."""
        return spec(None, None, None)

    def hidden_seq(self) -> PartitionSpec:
        """Ring-prefill activations [B, T, D]: T over the composite
        sequence axis."""
        return spec(None, self.seq_axes(), None)

    def heads_seq(self) -> PartitionSpec:
        """Ring-prefill q/k/v [B, T, Hx, hd]: T over the sequence axis."""
        return spec(None, self.seq_axes(), None, None)

    def logits(self) -> PartitionSpec:
        """[B, V] — vocab over tp, matching the column-sharded lm_head."""
        return spec(None, self.tp)


def kv_payload_shardings(mesh: Mesh, keys) -> Dict[str, NamedSharding]:
    """Per-key shardings for a KV block-transfer payload dict: ``k``/``v``
    pages get :meth:`SpecLayout.kv_blocks`, ``ks``/``vs`` scale planes get
    :meth:`SpecLayout.kv_scale_blocks`."""
    lay = SpecLayout.for_mesh(mesh)
    page = NamedSharding(mesh, lay.kv_blocks())
    scale = NamedSharding(mesh, lay.kv_scale_blocks())
    return {k: (scale if k in ("ks", "vs") else page) for k in keys}
