"""Model + engine configuration.

``ModelConfig`` describes a decoder-only transformer: the one Llama-class
block in every layer (Llama 2/3, Mistral, TinyLlama-style test models), or a
table of layer kinds under the published keys of a ``config.json`` that has
one. ``EngineConfig`` carries the
serving-side knobs that the reference exposes through engine flags and the
ModelRuntimeConfig (ref: lib/llm/src/local_model/runtime_config.rs:9 —
``total_kv_blocks``, ``max_num_seqs``, ``max_num_batched_tokens``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional, Tuple


def _freeze(v: Any) -> Any:
    """A JSON value as something hashable: a ``ModelConfig`` is a static
    argument of ``jax.jit``, and a configuration's file hands nested lists
    and objects through as they are."""
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v


# what a layer kind keeps for a sequence (``ModelConfig.layer_types``):
# K and V pages that grow with the context; one latent a token in pages of
# the same block table; or a state of fixed size held by the sequence's seat
KV_KINDS = ("full_attention", "sliding_attention")
LATENT_KIND = "mla_attention"
STATE_KIND = "linear_attention"


class AttnKind(NamedTuple):
    """One kind of attention layer of a table: its stack of ``wq`` / ``wo``
    / gate, its mask and its rope."""

    name: str          # the ``layer_types`` value, and the rope's key
    num_heads: int
    window: int        # keys i - j >= window are masked; 0 = none
    layers: Tuple[int, ...]   # the model's layers of this kind, in order


class LayerEntry(NamedTuple):
    """One row of the table: which stacks layer ``l`` reads, and where."""

    attn: int          # index into ``ModelConfig.attn_kinds``
    attn_at: int       # row of that kind's stacks, and of its cache lists
    ffn: str           # "dense" | "sparse"
    ffn_at: int        # row of that FFN kind's stacks


@dataclass(frozen=True)
class ModelConfig:
    """A decoder-only transformer's shapes.

    Without ``layer_types`` every layer is the Llama-class block: RMSNorm,
    grouped-query attention with rope, a SwiGLU (or the capacity experts of
    ``num_experts``).  With it the model is a table, one row a layer: K / V
    attention with or without a window, latent (MLA) attention with or
    without a q-LoRA, delta-rule linear attention; a dense SwiGLU or routed
    experts beside a shared one, whose router may also choose zero-compute
    experts and whose sum may join the stream a row later (``moe_shortcut``:
    two rows are one published double layer).  The comment below says which
    field is whose."""

    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_position: int = 8192
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # MoE (0 experts = dense). gpt-oss-class models set these.
    num_experts: int = 0
    num_experts_per_token: int = 0
    moe_capacity_factor: float = 2.0
    # A table of layer kinds, under the published keys of a ``config.json``
    # that has one.  Empty = every layer is the one Llama-class block above
    # and none of the fields below is read.  With a table: per layer the
    # attention kind, the FFN kind and the query heads; per attention kind
    # the window (``sliding_attention`` layers take ``sliding_window``) and
    # the rope (``rope_parameters[kind]``: ``rope_theta``,
    # ``partial_rotary_factor`` and, for ``rope_type`` "yarn", ``factor``,
    # ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    # ``attention_factor``).  ``attn_gate`` "per-head" multiplies each
    # query head's output by ``sigmoid(x Wg)``.  A "sparse" layer routes
    # over ``num_routed_experts`` by softmax, keeps every token's
    # ``num_experts_per_token`` choices, and computes those of the
    # ``num_experts`` it holds: shard ``expert_shard["index"]`` of
    # ``expert_shard["of"]`` equal ranges (parallel/moe.py: routed_ffn);
    # beside them one shared expert on every token.  "dense" layers are the
    # SwiGLU of ``intermediate_size``.
    #
    # Two more kinds keep no K and V (PR 34).  "mla_attention" (DeepSeek-V2):
    # ``kv_lora_rank`` + ``qk_rope_head_dim`` values a token in a paged
    # latent plane, queries of ``qk_nope_head_dim`` + ``qk_rope_head_dim``,
    # values of ``v_head_dim``; its rope (``rope_parameters[kind]``) turns
    # interleaved pairs where ``interleave`` is set.  "linear_attention"
    # (Kimi Delta Attention): per sequence a float32 state ``[heads,
    # head_dim, head_dim]`` and the last ``short_conv_kernel_size - 1``
    # inputs of its convolution, whatever the context, held by the
    # scheduler's seat; ``kda_lower_bound`` bounds a token's log decay and
    # ``state_dtype`` is what the state is kept in between steps.
    # The router scores by ``score_function`` ("softmax" | "sigmoid"); with
    # ``n_group`` > 0 the choice is limited to the ``topk_group`` best
    # groups, and ``moe_router_enable_expert_bias`` adds a bias to the
    # scores for the choice alone (DeepSeek-V3's ``noaux_tc``).
    #
    # A second rule for "linear_attention" (the gated delta rule,
    # arXiv:2412.06464; PR 45), chosen by ``linear_decay`` "softplus_head":
    # ``linear_key_head_dim`` x ``linear_value_head_dim`` states a head (not
    # ``head_dim`` square), ONE log decay a head ``-exp(A_log) softplus(x Wa
    # + dt_bias)`` (no bound: a scalar gate needs none),
    # ``linear_conv_kernel_dim`` taps, a step size ``sigmoid(x Wb)`` times 2
    # where ``linear_allow_neg_eigval``, and ``linear_gate`` "silu": the
    # normed output times ``silu(x Wg)`` a channel.  "" is KDA, which reads
    # none of them.  A K / V kind whose ``rope_parameters`` entry has
    # ``rope_type`` "none" turns nothing (the recurrent layers carry order).
    # ``qk_norm`` "projection": RMSNorm over the whole q and the whole k
    # projection before the heads are split.  ``norm_placement`` "post": a
    # sublayer reads the raw stream and its OUTPUT is normed, ``h + norm(
    # Mixer(h))`` (Olmo 2's reordered norm); "pre" is everyone else's.
    #
    # A latent layer with ``q_lora_rank`` > 0 makes its queries in two steps,
    # ``rmsnorm(x Wqa) Wqb``; ``mla_scale_q_lora`` / ``mla_scale_kv_lora``
    # multiply the queries by ``sqrt(hidden_size / q_lora_rank)`` and the
    # normed latent by ``sqrt(hidden_size / kv_lora_rank)`` (LongCat-Flash).
    # ``zero_expert_num`` > 0 widens the router behind the routed experts:
    # an index >= ``num_routed_experts`` is a zero-compute expert whose
    # output is its input (``zero_expert_type`` "identity"), computed where
    # the token lives, on every shard alike.  ``moe_shortcut`` (shortcut-
    # connected experts, arXiv:2509.01322): a "sparse" row's routed sum does
    # not join the stream at that row but after the FFN of the "dense" row
    # that follows it, so that row's attention and FFN never see it; the
    # sparse row's shared expert is then the double layer's first dense FFN.
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    num_heads_per_layer: Tuple[int, ...] = ()
    sliding_window: int = 0
    rope_parameters: Any = None
    attn_gate: str = ""
    num_routed_experts: int = 0
    expert_shard: Any = None
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 1.0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    short_conv_kernel_size: int = 0
    kda_lower_bound: float = 0.0
    state_dtype: str = "float32"
    score_function: str = "softmax"
    n_group: int = 0
    topk_group: int = 0
    moe_router_enable_expert_bias: bool = False
    q_lora_rank: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    zero_expert_num: int = 0
    zero_expert_type: str = "identity"
    moe_shortcut: bool = False
    linear_decay: str = ""
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_allow_neg_eigval: bool = False
    linear_gate: str = ""
    qk_norm: str = ""
    norm_placement: str = "pre"

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types", "num_heads_per_layer",
                     "rope_parameters", "expert_shard"):
            object.__setattr__(self, name, _freeze(getattr(self, name)))
        if not self.has_table:
            return
        L = self.num_layers
        for name in ("layer_types", "mlp_layer_types", "num_heads_per_layer"):
            if len(getattr(self, name)) != L:
                raise ValueError(
                    f"{name} has {len(getattr(self, name))} entries for "
                    f"{L} layers")
        if self.attn_gate not in ("", "per-head", "head_wise"):
            raise ValueError(f"unknown attn_gate {self.attn_gate!r}")
        if self.qk_norm not in ("", "projection"):
            raise ValueError(f"unknown qk_norm {self.qk_norm!r}")
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(
                f"unknown norm_placement {self.norm_placement!r}")
        ropes = dict(self.rope_parameters or ())
        for kind in self.attn_kinds:
            if kind.name not in KV_KINDS + (LATENT_KIND, STATE_KIND):
                raise ValueError(f"unknown layer type {kind.name!r}")
            if kind.name == STATE_KIND:
                if self.state_dtype not in ("float32", "bfloat16"):
                    raise ValueError(
                        f"unknown state_dtype {self.state_dtype!r}")
                if self.linear_decay not in ("", "softplus_head"):
                    raise ValueError(
                        f"unknown linear_decay {self.linear_decay!r}")
                if self.gated_delta:
                    if min(self.linear_key_head_dim,
                           self.linear_value_head_dim) < 1 \
                            or self.linear_conv_kernel_dim < 2:
                        raise ValueError(
                            "linear_decay 'softplus_head' (the gated delta "
                            "rule) needs linear_key_head_dim, "
                            "linear_value_head_dim and "
                            "linear_conv_kernel_dim >= 2")
                    if self.linear_gate not in ("", "silu"):
                        raise ValueError(
                            f"unknown linear_gate {self.linear_gate!r}")
                    continue
                if (self.linear_key_head_dim or self.linear_value_head_dim
                        or self.linear_conv_kernel_dim
                        or self.linear_allow_neg_eigval or self.linear_gate):
                    raise ValueError(
                        "the linear_* fields belong to linear_decay "
                        "'softplus_head'; Kimi Delta Attention has square "
                        "states of head_dim and reads none of them")
                if (self.short_conv_kernel_size < 2
                        or not self.kda_lower_bound < 0):
                    raise ValueError(
                        "linear_attention needs short_conv_kernel_size >= 2 "
                        "and a negative kda_lower_bound")
                continue
            if kind.name not in ropes:
                raise ValueError(
                    f"rope_parameters has no {kind.name!r} (a kind without "
                    f"a rotary embedding says so: rope_type 'none')")
            if self.qk_norm and kind.name == LATENT_KIND:
                raise ValueError("qk_norm is written for K / V kinds")
            if kind.name == LATENT_KIND:
                if min(self.kv_lora_rank, self.qk_nope_head_dim,
                       self.qk_rope_head_dim, self.v_head_dim) < 1:
                    raise ValueError(
                        "mla_attention needs kv_lora_rank, qk_nope_head_dim, "
                        "qk_rope_head_dim and v_head_dim")
                continue
            if kind.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"{kind.num_heads} query heads over "
                    f"{self.num_kv_heads} KV heads")
            if kind.window < 0 or (kind.name == "sliding_attention"
                                   and kind.window == 0):
                raise ValueError("sliding_attention needs sliding_window")
        if self.norm_placement == "post" and (
                "sparse" in self.mlp_layer_types
                or LATENT_KIND in self.layer_types
                or (STATE_KIND in self.layer_types
                    and not self.gated_delta)):
            raise ValueError(
                "norm_placement 'post' is written for K / V and gated-"
                "delta-rule layers with a dense FFN")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"unknown mlp layer type in "
                             f"{self.mlp_layer_types}")
        if "sparse" in self.mlp_layer_types:
            start, held = self.experts_held
            if held != self.num_experts or held < 1:
                raise ValueError(
                    f"num_experts {self.num_experts} is not shard "
                    f"{dict(self.expert_shard or ())} of "
                    f"{self.num_routed_experts} routed experts")
            if not 0 < self.num_experts_per_token <= self.num_routed_experts:
                raise ValueError("num_experts_per_token out of range")
            if self.moe_intermediate_size < 1 \
                    or self.shared_expert_intermediate_size < 1:
                raise ValueError("a sparse layer needs its expert widths")
            if self.score_function not in ("softmax", "sigmoid"):
                raise ValueError(
                    f"unknown score_function {self.score_function!r}")
            if self.zero_expert_num < 0 or (
                    self.zero_expert_num
                    and self.zero_expert_type != "identity"):
                raise ValueError(
                    f"zero-compute experts are identities, not "
                    f"{self.zero_expert_type!r}")
            if self.zero_expert_num and self.n_group:
                raise ValueError("a group limit over a router with "
                                 "zero-compute experts is not defined")
            if self.moe_shortcut and any(
                    f == "sparse" and self.mlp_layer_types[li + 1:li + 2]
                    != ("dense",)
                    for li, f in enumerate(self.mlp_layer_types)):
                raise ValueError(
                    "moe_shortcut: every sparse row is followed by the "
                    "dense row its routed sum joins behind, not "
                    f"{self.mlp_layer_types}")
            if self.n_group and (
                    self.num_routed_experts % self.n_group
                    or not 0 < self.topk_group <= self.n_group
                    or self.num_experts_per_token
                    > self.topk_group * (self.num_routed_experts
                                         // self.n_group)):
                raise ValueError(
                    f"n_group {self.n_group} / topk_group {self.topk_group} "
                    f"do not divide {self.num_routed_experts} routed experts "
                    f"for {self.num_experts_per_token} a token")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def has_table(self) -> bool:
        return bool(self.layer_types)

    @property
    def is_moe(self) -> bool:
        """The capacity-dispatch expert FFN in every layer (moe_ffn); a
        table's sparse layers are ``has_routed_experts``."""
        return self.num_experts > 0 and not self.has_table

    @property
    def has_routed_experts(self) -> bool:
        return "sparse" in self.mlp_layer_types

    @property
    def has_seat_state(self) -> bool:
        """Some layer keeps a per-sequence state in the scheduler's seat:
        nothing of a sequence's past can be skipped or moved without it."""
        return STATE_KIND in self.layer_types

    @property
    def gated_delta(self) -> bool:
        """The linear layers follow the gated delta rule (one decay a head,
        ``linear_key_head_dim`` x ``linear_value_head_dim`` states), not
        Kimi Delta Attention."""
        return self.linear_decay == "softplus_head"

    @property
    def has_latent_cache(self) -> bool:
        return LATENT_KIND in self.layer_types

    @property
    def cache_kinds(self) -> Tuple[str, ...]:
        """What ``model.init_cache`` builds, by what the layers keep."""
        kinds = set(self.layer_types) or {"full_attention"}
        return tuple(name for name, on in (
            ("kv", kinds & set(KV_KINDS)), ("latent", LATENT_KIND in kinds),
            ("state", STATE_KIND in kinds)) if on)

    @property
    def attn_kinds(self) -> Tuple[AttnKind, ...]:
        """The table's attention kinds in order of first appearance (one
        nameless kind of ``num_heads`` without a table)."""
        if not self.has_table:
            return (AttnKind("", self.num_heads, 0,
                             tuple(range(self.num_layers))),)
        kinds: Dict[str, AttnKind] = {}
        for li, (name, heads) in enumerate(
                zip(self.layer_types, self.num_heads_per_layer)):
            k = kinds.get(name)
            if k is None:
                window = (self.sliding_window
                          if name == "sliding_attention" else 0)
                kinds[name] = AttnKind(name, heads, window, (li,))
            elif k.num_heads != heads:
                raise ValueError(
                    f"layer {li}: {heads} query heads in a {name} layer, "
                    f"{k.num_heads} in an earlier one (one stack a kind)")
            else:
                kinds[name] = k._replace(layers=k.layers + (li,))
        return tuple(kinds.values())

    @property
    def layer_table(self) -> Tuple[LayerEntry, ...]:
        kinds = self.attn_kinds
        if not self.has_table:
            return tuple(LayerEntry(0, li, "dense", li)
                         for li in range(self.num_layers))
        of = {k.name: i for i, k in enumerate(kinds)}
        seen = {"dense": 0, "sparse": 0}
        rows = []
        for li, (a, f) in enumerate(
                zip(self.layer_types, self.mlp_layer_types)):
            rows.append(LayerEntry(of[a], kinds[of[a]].layers.index(li),
                                   f, seen[f]))
            seen[f] += 1
        return tuple(rows)

    @property
    def router_width(self) -> int:
        """Outputs of a sparse layer's router: the routed experts, then the
        zero-compute ones."""
        return self.num_routed_experts + self.zero_expert_num

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this shard holds."""
        shard = dict(self.expert_shard or (("index", 0), ("of", 1)))
        count = self.num_routed_experts // shard["of"]
        return shard["index"] * count, count

    def rope_of(self, kind: AttnKind) -> Dict[str, Any]:
        """The rope of a table's attention kind, as ``model._rope_kind``
        takes it."""
        return dict(dict(self.rope_parameters)[kind.name])

    # -- canned configs ---------------------------------------------------

    @staticmethod
    def llama3_8b() -> "ModelConfig":
        return ModelConfig()

    @staticmethod
    def llama3_70b() -> "ModelConfig":
        return ModelConfig(
            hidden_size=8192, intermediate_size=28672, num_layers=80,
            num_heads=64, num_kv_heads=8,
        )

    @staticmethod
    def llama3_1b() -> "ModelConfig":
        """Llama-3.2-1B shapes — fits one small chip comfortably."""
        return ModelConfig(
            hidden_size=2048, intermediate_size=8192, num_layers=16,
            num_heads=32, num_kv_heads=8, head_dim=64,
            tie_word_embeddings=True,
        )

    @staticmethod
    def mixtral_8x7b() -> "ModelConfig":
        """Mixtral-class MoE shapes (8 experts, top-2 routing)."""
        return ModelConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8,
            rope_theta=1e6, num_experts=8, num_experts_per_token=2,
        )

    @staticmethod
    def tiny_moe(vocab_size: int = 512) -> "ModelConfig":
        """CPU-testable MoE toy (8 experts over an 8-way mesh)."""
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=8, num_kv_heads=8, head_dim=8,
            max_position=512, rope_theta=10000.0, dtype="float32",
            num_experts=8, num_experts_per_token=2,
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "ModelConfig":
        """CPU-testable toy config (shapes divisible by an 8-way mesh)."""
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=8, num_kv_heads=8, head_dim=8,
            max_position=512, rope_theta=10000.0, dtype="float32",
        )


DECODE_BUCKETS = (8, 16, 32, 64)


@dataclass(frozen=True)
class EngineConfig:
    """Serving-side engine knobs (vLLM-equivalent semantics)."""

    block_size: int = 16                # tokens per KV block
    num_blocks: int = 2048              # total KV blocks in HBM (G1 tier)
    max_num_seqs: int = 64              # max concurrently running sequences
    # prompt tokens one round may prefill, in chunks of at most the largest
    # prefill bucket; decode rows run in their own program, take none of it
    # and are bounded by ``max_num_seqs``
    max_num_batched_tokens: int = 512
    watermark: float = 0.01             # min free-block fraction before admit
    max_model_len: int = 8192           # max tokens per sequence
    enable_prefix_caching: bool = True
    # decode batch sizes are padded up to the nearest bucket so XLA compiles
    # a handful of programs, not one per batch size.  Left empty it is
    # ``DECODE_BUCKETS``, and past 64 seats further powers of two up to
    # ``max_num_seqs`` (128 seats add one rung)
    decode_buckets: Tuple[int, ...] = ()
    # prefill chunk lengths likewise bucketed (powers of two)
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256, 512)
    # sharding: (dp, tp) or (dp, fsdp, tp) mesh axis sizes; (1, 1) =
    # single chip. Axis semantics live in parallel/layout.py (SpecLayout)
    mesh_shape: Tuple[int, ...] = (1, 1)
    # attention implementation of the decode step: "pallas" streams KV
    # blocks HBM→VMEM with online softmax (ops/paged_attention.py);
    # "einsum" materialises the gathered context (the XLA reference path,
    # and the program the stall watchdog rebuilds a wedged window on).
    # The kernel's tile is not a knob: ops.paged_attention.default_kv_tile
    # derives it from the shapes a launch sees.
    attention_impl: str = "pallas"
    # the T>1 shape classes (spec windows, prefill chunks) run einsum
    # unless overridden here ("" = einsum); no cell selects the kernel
    # there yet, only tests do
    attention_impl_spec: str = ""
    attention_impl_prefill: str = ""
    # chunked prefill: cap each prefill chunk at this many tokens so long
    # prompts are admitted in slices interleaved with running decodes under
    # max_num_batched_tokens, instead of one whole-prompt stall that blows
    # up TTFT p99 for everyone behind it. 0 = off (chunks capped only by
    # the largest prefill bucket).
    prefill_chunk_tokens: int = 0
    # run-ahead: how many scheduled windows may be in flight before the
    # engine loop waits for a landing. >1 dispatches window N+1 (decode
    # input tokens read from the device ring) while window N's sampled
    # tokens are still being fetched, so a host sync never sits on the
    # dispatch path (premise: one sync ~64 ms vs a ~3 ms decode step —
    # measured on an earlier transport; re-measured by chip_smoke.py, see
    # CHANGES). 1 = classic synchronous loop (pp engines force 1).
    pipeline_depth: int = 2
    # pipeline parallelism: >1 runs the unified step GPipe-style over a
    # ``pp`` mesh of that many stages (layers stage-sharded, decode
    # batches microbatched; parallel/pp_serving.py). Mutually exclusive
    # with (dp, tp) mesh_shape > (1, 1).
    pp_stages: int = 1
    pp_microbatches: int = 4
    # sequence-parallel prefill: a fresh prompt at least this long is
    # prefilled as ONE chunk with its T axis sharded over all mesh devices
    # (ring attention over a flat "sp" view of the dp×tp device set), so
    # activation memory is O(T / n_devices) and BASELINE's 8k-ISL shapes
    # don't have to fit one chip's budget. 0 = disabled (chunked prefill).
    sp_prefill_threshold: int = 0
    # speculative decoding: "ngram" replaces each decode window with a
    # draft+verify window — a device-resident prompt-lookup drafter proposes
    # up to spec_k continuation tokens from the seq's own on-device token
    # history and ONE ragged [B, k+1] forward verifies them, so a single
    # host round-trip can land up to k+1 tokens. Greedy rows get exact
    # parity with spec_mode="off"; sampled rows emit 1 token per window.
    spec_mode: str = "off"              # "off" | "ngram"
    spec_k: int = 4                     # max draft tokens per window
    spec_ngram_min: int = 1             # smallest suffix n-gram to match
    spec_ngram_max: int = 3             # largest suffix n-gram to match
    # adaptive kill switch: once spec_auto_disable_window draft tokens have
    # been verified, an acceptance rate below the threshold permanently
    # falls back to plain autopilot windows (0.0 = never disable)
    spec_auto_disable_threshold: float = 0.0
    spec_auto_disable_window: int = 256
    # device token-history capacity per seat (0 = max_model_len); drafting
    # only sees the first spec_hist_cap positions of each sequence
    spec_hist_cap: int = 0
    # engine stall watchdog: a dispatched window whose results don't land
    # within stall_timeout_s + stall_timeout_per_token_s * real tokens is
    # declared wedged — the window is cancelled, its shape class
    # quarantined, and its seats recovered by recompute. 0 = watchdog off.
    stall_timeout_s: float = 0.0
    stall_timeout_per_token_s: float = 0.0
    # per-seat recompute retries after a stall before the seat errors out
    stall_seq_retries: int = 2
    # consecutive stalled windows before the worker declares itself dead
    # (aborts every seat so drain + Migration take over)
    stall_dead_threshold: int = 3
    # HBM-pressure ladder: graduated response to KV pool occupancy,
    # engaged per rung when usage crosses its threshold (0.0 = rung off).
    # rung 1: spill the coldest pending-free seat to the host pool (or
    # plain recompute without kvbm); rung 2: pause speculative windows;
    # rung 3: shed new admissions until pressure releases.
    pressure_spill_threshold: float = 0.0
    pressure_spec_threshold: float = 0.0
    pressure_shed_threshold: float = 0.0
    # hysteresis: a rung releases once usage < threshold - pressure_release
    pressure_release: float = 0.05
    # quantized serving (engine/quant.py): "bf16" keeps the model dtype
    # end to end (byte-identical to the pre-quant code path); "int8"/"fp8"
    # store weights / paged KV in 1 byte per element with per-channel
    # (weights) or per-token-per-head (KV) float32 scales riding the same
    # pytrees. Validated here so a bad dtype fails at startup, not at the
    # first dispatch.
    weight_dtype: str = "bf16"          # "bf16" | "int8" | "fp8"
    kv_dtype: str = "bf16"              # "bf16" | "int8" | "fp8"

    def __post_init__(self):
        if len(self.mesh_shape) not in (2, 3):
            raise ValueError("mesh_shape must be (dp, tp) or (dp, fsdp, tp)")
        mesh_devices = 1
        for n in self.mesh_shape:
            mesh_devices *= n
        if self.pp_stages > 1 and mesh_devices > 1:
            raise ValueError("pp_stages and a (dp, tp) mesh are exclusive")
        if not self.decode_buckets:
            ladder = list(DECODE_BUCKETS)
            while ladder[-1] < self.max_num_seqs:
                ladder.append(2 * ladder[-1])
            object.__setattr__(self, "decode_buckets", tuple(ladder))
        if self.max_num_seqs > max(self.decode_buckets):
            raise ValueError("max_num_seqs exceeds largest decode bucket")
        if self.spec_mode not in ("off", "ngram"):
            raise ValueError(f"unknown spec_mode {self.spec_mode!r}")
        if self.attention_impl not in ("pallas", "einsum"):
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r}"
            )
        for cls in ("spec", "prefill"):
            v = getattr(self, f"attention_impl_{cls}")
            if v not in ("", "pallas", "einsum"):
                raise ValueError(
                    f"unknown attention_impl_{cls} {v!r}"
                )
        if self.prefill_chunk_tokens < 0:
            raise ValueError("prefill_chunk_tokens must be >= 0")
        if self.spec_mode != "off":
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if not (1 <= self.spec_ngram_min <= self.spec_ngram_max):
                raise ValueError("need 1 <= spec_ngram_min <= spec_ngram_max")
            if self.pp_stages > 1:
                raise ValueError("spec_mode requires pp_stages == 1")
        if self.stall_timeout_s < 0 or self.stall_timeout_per_token_s < 0:
            raise ValueError("stall timeouts must be >= 0")
        if self.stall_seq_retries < 0:
            raise ValueError("stall_seq_retries must be >= 0")
        if self.stall_dead_threshold < 1:
            raise ValueError("stall_dead_threshold must be >= 1")
        for rung in ("spill", "spec", "shed"):
            v = getattr(self, f"pressure_{rung}_threshold")
            if not (0.0 <= v <= 1.0):
                raise ValueError(
                    f"pressure_{rung}_threshold must be in [0, 1]"
                )
        if self.pressure_release < 0:
            raise ValueError("pressure_release must be >= 0")
        for knob in ("weight_dtype", "kv_dtype"):
            v = getattr(self, knob)
            if v not in ("bf16", "int8", "fp8"):
                raise ValueError(
                    f"unknown {knob} {v!r} (expected bf16|int8|fp8)"
                )
        if (self.weight_dtype != "bf16" or self.kv_dtype != "bf16") \
                and self.pp_stages > 1:
            raise ValueError("quantized serving requires pp_stages == 1")
        # max_num_batched_tokens counts a round's prompt tokens only, and
        # may exceed the largest prefill bucket: the scheduler caps each
        # chunk at the bucket, and what is left goes to the next prompt

    @property
    def max_blocks_per_seq(self) -> int:
        return (self.max_model_len + self.block_size - 1) // self.block_size
