"""Ring sequence-parallel attention vs single-device reference.

Runs on the 8-device virtual CPU mesh from conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.parallel import make_ring_attention


def _full_attention(q, k, v, causal: bool):
    """The reference: vanilla attention on one device, f32 accumulation.
    q: [B, T, H, hd], k/v: [B, T, KV, hd] (GQA: H % KV == 0)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, T, KV, G, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("btkgh,bskh->btkgs", qf, kf) / np.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskh->btkgh", p, vf).reshape(B, T, H, hd)
    return out.astype(q.dtype)


def _mesh(n=8, axis="sp"):
    devs = np.asarray(jax.devices()[:n])
    return Mesh(devs, (axis,))


def _inputs(B=2, T=64, H=8, KV=4, hd=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, T, KV, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, T, KV, hd)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    mesh = _mesh()
    q, k, v = _inputs()
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    got = make_ring_attention(mesh, causal=causal)(qs, ks, vs)
    want = _full_attention(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_ring_gqa_grouping():
    """GQA: ring output must match per-group full attention, not leak
    across kv groups."""
    mesh = _mesh()
    q, k, v = _inputs(H=8, KV=2, seed=3)
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    got = make_ring_attention(mesh)(
        jax.device_put(q, spec), jax.device_put(k, spec),
        jax.device_put(v, spec),
    )
    want = _full_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_ring_output_stays_sharded():
    mesh = _mesh()
    q, k, v = _inputs()
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    got = make_ring_attention(mesh)(
        jax.device_put(q, spec), jax.device_put(k, spec),
        jax.device_put(v, spec),
    )
    assert got.sharding.spec == P(None, "sp", None, None)
