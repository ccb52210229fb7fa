"""What the readers of a table's routed experts share (not a reader: the
name starts with _): the window's decode records that carry the routing
counters (StepStats ``moe_pairs`` ..., read from the decode window's own
fetch).  A program without the counters gives nothing."""


def decode_records(ctx):
    return [r for r in ctx["steps"]
            if r.get("kind") == "decode" and r.get("moe_pairs")]


def held_experts(ctx) -> int:
    """Routed experts the model holds, over its sparse layers (of the
    rehearsal model in a rehearsal)."""
    cfg = ctx["config"]
    if ctx["rehearse"]:
        cfg = cfg["rehearse"]["model"]
    sparse = sum(1 for k in cfg.get("mlp_layer_types", ()) if k == "sparse")
    return cfg.get("num_experts", 0) * sparse
