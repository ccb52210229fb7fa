"""The ONE analytic FLOPs/parameter model of the flight recorder
(``observability/stepstats.py``).

Two terms per processed token:

* **matmul**: ``2 * n_active_params`` — every weight participates in one
  multiply-accumulate per token (2 FLOPs/MAC). The embedding *lookup* is
  excluded (it is a gather, not a matmul); the lm_head projection is
  included. MoE models count only the ``num_experts_per_token`` routed
  experts as active.
* **attention**: ``4 * num_layers * num_heads * head_dim * context`` —
  the QK^T scores plus the PV mix, both ``num_heads * head_dim * context``
  MACs per query token. This is the term the old ``2·N·tokens`` formula
  dropped; at long contexts it dominates.

Peak FLOP/s per chip comes from public spec sheets (dense bf16; fp32
halves the MXU rate).
"""

from __future__ import annotations

from typing import Optional

# engine/config.py's names of the kinds (importing them from there would
# import the engine, which imports this module)
STATE_KIND, LATENT_KIND = "linear_attention", "mla_attention"

# Peak dense bf16 FLOP/s per chip by device kind (public spec sheets).
PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}
# Peak dense int8 OP/s per chip. v5e/v5p/v6e double the bf16 MXU rate on
# 8-bit inputs; v4 predates the int8 path and stays at its bf16 number.
PEAK_FLOPS_INT8 = {
    "v4": 275e12,
    "v5 lite": 394e12,
    "v5e": 394e12,
    "v5p": 918e12,
    "v5": 918e12,
    "v6 lite": 1836e12,
    "v6e": 1836e12,
}
# fp8 rides the same 8-bit MXU datapath as int8 on the generations that
# have it (MFU with quantized weights is measured against this roofline).
PEAK_FLOPS_FP8 = PEAK_FLOPS_INT8


def peak_flops(device_kind: str, platform: str,
               dtype: str = "bfloat16") -> Optional[float]:
    """Per-chip peak FLOP/s for a device kind string (e.g. ``"TPU v5e"``).

    Longest-key match over the table.  A TPU kind the table does not hold
    is an error — add it with its source; a default would turn every
    utilisation computed from it into a made-up number.  A non-TPU platform
    has no peak (``None``): MFU is then absent, not nominal.  fp32 halves a
    TPU's MXU rate; ``"int8"``/``"fp8"`` select the doubled 8-bit table
    (bf16 inputs are the spec-sheet number)."""
    if platform != "tpu":
        return None
    kind = (device_kind or "").lower()
    table = (PEAK_FLOPS_INT8 if dtype in ("int8", "fp8", "float8_e4m3fn")
             else PEAK_FLOPS)
    for key in sorted(table, key=len, reverse=True):
        if key in kind:
            peak = table[key]
            break
    else:
        raise ValueError(
            f"no published peak FLOP/s for TPU device kind {device_kind!r}; "
            f"add it to observability/flops.py with its source")
    if dtype in ("float32", "f32"):
        peak /= 2.0
    return peak


def param_count(cfg) -> int:
    """Exact parameter count of ``engine.model.init_params`` for a
    ModelConfig (checked against the real tree in test_observability)."""
    if cfg.has_table:
        return _table_param_count(cfg)
    hd = cfg.head_dim_
    D, H, KV, F, L, V = (
        cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
        cfg.intermediate_size, cfg.num_layers, cfg.vocab_size,
    )
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D   # wq, wk, wv, wo
    if cfg.is_moe:
        mlp = D * cfg.num_experts + 3 * cfg.num_experts * D * F
    else:
        mlp = 3 * D * F
    per_layer = attn + mlp + 2 * D                      # + the two norms
    total = V * D + L * per_layer + D                   # embed + final_norm
    if not cfg.tie_word_embeddings:
        total += D * V
    return total


def _table_param_count(cfg, active: bool = False) -> int:
    """``param_count`` of a table of layer kinds (``ModelConfig.
    layer_types``), layer by layer: the attention of the layer's kind (its
    own query heads, the per-head gate, a latent layer's q-LoRA), then the
    dense SwiGLU or the router over every output (routed and zero-compute),
    the routed experts held here and the shared expert (a ``moe_shortcut``
    double layer is its two rows: two latent attentions, the shared expert
    as its first dense FFN, the dense row's as its second).  ``active``:
    what multiplies one token instead — of the routed experts the
    ``num_experts_per_token`` a token chooses, times the share of the
    router's outputs that is held (the rest of its choices are computed on
    the shards that hold them, or are zero-compute), and the head but not
    the embedding's gather."""
    hd, D, KV, V = (cfg.head_dim_, cfg.hidden_size, cfg.num_kv_heads,
                    cfg.vocab_size)
    kinds = cfg.attn_kinds
    total = D                                            # final_norm
    if not active:
        total += V * D                                   # embed
    if active or not cfg.tie_word_embeddings:
        total += D * V                                   # head
    for entry in cfg.layer_table:
        kind = kinds[entry.attn]
        H = kind.num_heads
        total += 2 * D                                   # the two norms
        if kind.name == STATE_KIND and cfg.gated_delta:
            # the gated delta rule: q, k of dk and v of dv a head in, the
            # output gate, wo out; decay and beta a head; the conv's taps;
            # the decay's rate and bias a head; the output norm
            dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
            total += D * H * (2 * dk + 2 * dv) + 2 * D * H + 2 * H + dv
            if cfg.linear_gate:
                total += D * H * dv
            total += cfg.linear_conv_kernel_dim * H * (2 * dk + dv)
        elif kind.name == STATE_KIND:
            # wq, k, v, decay in; wo out; beta; the conv's taps; the
            # decay's rate and bias; the output norm
            total += 5 * D * H * hd + D * H + H + H * hd + hd
            total += cfg.short_conv_kernel_size * 3 * H * hd
        elif kind.name == LATENT_KIND:
            r, dn, dr, dvh = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                              cfg.qk_rope_head_dim, cfg.v_head_dim)
            ql = cfg.q_lora_rank
            if ql:                       # Wqa, its norm, Wqb
                total += D * ql + ql + ql * H * (dn + dr)
            else:
                total += D * H * (dn + dr)                 # wq
            total += D * (r + dr) + r                      # wdkv, norm
            total += r * H * (dn + dvh) + H * dvh * D      # wukv, wo
        else:
            total += 2 * D * H * hd + 2 * D * KV * hd    # wq, wo, wk, wv
            if cfg.qk_norm:                              # over a projection
                total += H * hd + KV * hd
        if cfg.attn_gate:
            total += D * H
        if entry.ffn == "dense":
            total += 3 * D * cfg.intermediate_size
            continue
        experts = cfg.num_experts
        if active:
            # a choice falls on a held expert with the held share of the
            # router's outputs; a zero-compute expert multiplies nothing
            experts = (cfg.num_experts_per_token * cfg.num_experts
                       / cfg.router_width)
        total += D * cfg.router_width                    # router
        if cfg.moe_router_enable_expert_bias and not active:
            total += cfg.router_width                    # its choice bias
        total += int(experts * 3 * D * cfg.moe_intermediate_size)
        total += 3 * D * cfg.shared_expert_intermediate_size
    return total


def active_param_count(cfg) -> int:
    """Parameters doing matmul work per token: the full count minus the
    embedding table (gather, not matmul), with MoE expert weights scaled
    to the ``num_experts_per_token`` actually routed."""
    if cfg.has_table:
        return _table_param_count(cfg, active=True)
    D, F, L, V = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_layers, cfg.vocab_size)
    active = param_count(cfg) - V * D
    if cfg.tie_word_embeddings:
        # the tied table still runs as the lm_head matmul
        active += D * V
    if cfg.is_moe and cfg.num_experts > cfg.num_experts_per_token:
        inactive_experts = cfg.num_experts - cfg.num_experts_per_token
        active -= L * inactive_experts * 3 * D * F
    return active


class FlopsModel:
    """Per-step forward-FLOPs estimator for one ModelConfig.

    ``step_flops(tokens, context_sum)`` = matmul term + attention term,
    where ``context_sum`` is the sum over the step's tokens of the context
    length each token attends (position + 1)."""

    def __init__(self, model_cfg):
        self.model_cfg = model_cfg
        self.n_params = param_count(model_cfg)
        self.n_active_params = active_param_count(model_cfg)
        self.matmul_per_token = 2.0 * self.n_active_params
        # QK^T + PV: 2 matmuls of (num_heads*head_dim x context) per token
        # (a table: each layer its kind's heads; a window's layers are
        # counted at the whole context, which is all a record carries for a
        # prefill: an upper bound there)
        # a latent layer's decode is absorbed: a position costs its heads
        # the latent's width for the score and its rank for the sum; a
        # linear-attention layer attends no context (its recurrence is 7
        # passes over a head's state a token, whatever the position)
        kinds = model_cfg.attn_kinds
        self.attn_coef = 0.0
        for e in model_cfg.layer_table:
            name, H = kinds[e.attn].name, kinds[e.attn].num_heads
            if name == STATE_KIND and model_cfg.gated_delta:
                self.matmul_per_token += 7.0 * H * (
                    model_cfg.linear_key_head_dim
                    * model_cfg.linear_value_head_dim)
            elif name == STATE_KIND:
                self.matmul_per_token += 7.0 * H * model_cfg.head_dim_ ** 2
            elif name == LATENT_KIND:
                self.attn_coef += 2.0 * H * (2 * model_cfg.kv_lora_rank
                                             + model_cfg.qk_rope_head_dim)
            else:
                self.attn_coef += 4.0 * model_cfg.head_dim_ * H

    def step_flops(self, tokens: float, context_sum: float) -> float:
        return self.matmul_per_token * tokens + self.attn_coef * context_sum

    def sequence_context_sum(self, length: int, start: int = 0) -> int:
        """Sum of (position + 1) over positions [start, start+length) —
        the ``context_sum`` of prefilling those tokens causally."""
        if length <= 0:
            return 0
        return length * start + length * (length + 1) // 2

    def sequence_flops(self, isl: int, osl: int) -> float:
        """Total forward FLOPs to serve one (isl, osl) request: prefill
        the prompt plus decode osl tokens, attention term included."""
        total = isl + osl
        return self.step_flops(total, self.sequence_context_sum(total))
