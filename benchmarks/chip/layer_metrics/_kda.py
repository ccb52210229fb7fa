"""What the readers of a table's linear-attention (KDA) and latent (MLA)
layers share (not a reader: the name starts with _): the window's decode
and prefill records that carry the two counters a program with such layers
stamps (StepStats ``state_rows``, ``latent_context_sum``), and the layer
counts and widths from the configuration's file.  A program without the
counters, or a configuration without the kinds, gives nothing."""

LINEAR = "linear_attention"
LATENT = "mla_attention"


def model_of(ctx) -> dict:
    """The model's keys as run (the rehearsal's in a rehearsal)."""
    cfg = ctx["config"]
    return cfg["rehearse"]["model"] if ctx["rehearse"] else cfg


def layers_of(ctx, kind: str) -> int:
    return sum(1 for k in model_of(ctx).get("layer_types", ()) if k == kind)


def records(ctx, kind: str, counter: str) -> list:
    return [r for r in ctx["steps"]
            if r.get("kind") == kind and r.get(counter)]
