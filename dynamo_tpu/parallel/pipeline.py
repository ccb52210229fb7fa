"""Pipeline parallelism: GPipe-style microbatched stage execution over a
``pp`` mesh axis.

The reference passes ``--pipeline-parallel-size`` through to its engines
(SURVEY §2.3); here it is a native building block. Stages are laid out one
per device along ``pp``; activations hop stage→stage via ``lax.ppermute``
(neighbor ICI/DCN traffic only — this is the axis to map onto DCN for
multi-pod, since exactly one activation tensor crosses the boundary per
microbatch per step). Classic GPipe schedule: with S stages and M
microbatches the bubble fraction is (S-1)/(S+M-1).

Contract: ``stage_fn(stage_params, x) -> y`` with ``x``/``y`` the same
shape/dtype (a residual-block stack); ``params`` leaves are stacked on a
leading stage axis sharded ``P("pp", ...)``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from . import layout
from .layout import AXIS_PP


def pipeline_stages(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,     # this device's stage params (leading axis sliced)
    x: jax.Array,          # [M, mb, ...] all microbatches (replicated input)
    axis_name: str = AXIS_PP,
) -> jax.Array:
    """Per-shard pipeline body — call inside ``shard_map``.

    Returns the final-stage outputs ``[M, mb, ...]`` (replicated to every
    stage via a masked psum at the end).
    """
    S = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = x.shape[0]
    fwd = [(j, (j + 1) % S) for j in range(S)]

    act = jnp.zeros_like(x[0])
    out = jnp.zeros_like(x)
    for t in range(M + S - 1):
        # stage 0 ingests microbatch t; everyone else uses the activation
        # handed over by its predecessor last step
        feed = x[t] if t < M else jnp.zeros_like(x[0])
        act = jnp.where(stage == 0, feed, act)
        # microbatch index this stage holds at time t (valid in-window)
        mb = t - stage
        valid = (mb >= 0) & (mb < M)
        y = stage_fn(stage_params, act)
        act = jnp.where(valid, y, act)
        # last stage banks its finished microbatch
        bank = (stage == S - 1) & valid
        out = jnp.where(
            bank & (jnp.arange(M) == jnp.clip(mb, 0, M - 1))[
                (slice(None),) + (None,) * (out.ndim - 1)
            ],
            act[None], out,
        )
        if t != M + S - 2:
            act = jax.lax.ppermute(act, axis_name, fwd)
    # replicate the last stage's banked outputs to all stages
    out = jnp.where(stage == S - 1, out, jnp.zeros_like(out))
    return jax.lax.psum(out, axis_name)


def make_pipeline(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    axis: str = AXIS_PP,
):
    """Jittable pipelined forward: ``f(params, x[M, mb, ...]) -> y``.

    ``params`` leaves must carry a leading stage axis of size
    ``mesh.shape[axis]`` (shard with :func:`stage_shardings`).
    """
    fn = functools.partial(pipeline_stages, axis_name=axis)

    def run(params, x):
        return fn(
            stage_fn,
            jax.tree.map(lambda p: p[0], params),  # shard_map slices stage
            x,
        )

    def wrapped(params, x):
        stage_spec = layout.spec(axis)
        return layout.shard_map(
            run, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: stage_spec, params),
                      layout.spec()),
            out_specs=layout.spec(),
        )(params, x)

    return jax.jit(wrapped)


def stage_shardings(mesh: Mesh, params: Any, axis: str = AXIS_PP) -> Any:
    """NamedShardings putting each leaf's leading (stage) axis on ``axis``."""
    stage = layout.named(mesh, axis)
    return jax.tree.map(lambda _: stage, params)
