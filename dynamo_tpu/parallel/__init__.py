"""Model-parallelism building blocks: mesh layouts (``layout``), SP/CP ring
attention (``ring_attention``), expert parallelism (``moe``), pipeline-
parallel serving (``pp_serving``) and multi-host replay (``multihost``).

The reference delegates intra-model parallelism to its engines (SURVEY §2.3:
TP/PP/EP via vLLM/SGLang flags; SP/CP absent upstream) — here the engine is
ours, so these are first-class TPU-native implementations over
``jax.sharding.Mesh`` axes.
"""

from .ring_attention import make_ring_attention, ring_attention

__all__ = [
    "ring_attention",
    "make_ring_attention",
]
