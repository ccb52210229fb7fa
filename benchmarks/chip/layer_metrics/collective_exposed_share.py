"""Device time covered by a collective (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) and by no other op, over
device busy time, mean over the chips: what the exchanges between chips cost
that nothing hides.  ``xplane.reduce_events`` computes both per device plane.
A trace without a collective op (one chip) gives nothing, never 0."""

SOURCE = "device_trace"
LAYER = "collectives"
UNIT = "%"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.xplane import COLLECTIVES

    t = ctx["trace"]
    if not t or not t["busy_s"]:
        return None
    if not any(name.startswith(COLLECTIVES) for name, _ in t["device_ops"]):
        return None
    return 100.0 * t["collective_exposed_s"] / t["busy_s"]
