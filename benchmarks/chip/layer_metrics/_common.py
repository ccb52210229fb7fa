"""Helpers shared by the readers (not a reader: the name starts with _)."""


from benchmarks.chip.metrics import percentile


def program_median_ms(trace, word):
    """Median device span of the programs whose name holds ``word``,
    weighted by how often each ran."""
    if not trace:
        return None
    spans = []
    for name, p in trace["programs"].items():
        if word in name:
            spans += [p["median_ms"]] * p["count"]
    return percentile(spans, 50)


def op_seconds(trace, word):
    return sum(s for name, s in trace["device_ops"] if word in name)


def idle_share(trace):
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def sibling_read(name, ctx):
    """``read`` of the reader ``<name>.py`` beside this file: a metric that
    is split by cell (``x.chat`` beside ``x``) measures the same thing and
    differs only in the end-to-end metric it should move."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location("sibling_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)
