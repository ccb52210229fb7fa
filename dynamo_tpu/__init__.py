"""dynamo-tpu: a TPU-native distributed LLM inference serving framework.

Capability-equivalent to NVIDIA Dynamo (see SURVEY.md) but designed TPU-first:
the model engine is JAX/XLA (pjit-sharded transformers, paged HBM KV cache,
Pallas kernels), intra-model parallelism rides ICI via jax.sharding, and the
KV-block data plane uses XLA collectives / device-to-device transfers instead
of NIXL RDMA. The host-side control plane (discovery, leases, request
transport, response streams) follows the reference's protocol shapes
(ref: lib/runtime/src/lib.rs, lib/llm/src/lib.rs).
"""

__version__ = "0.1.0"
