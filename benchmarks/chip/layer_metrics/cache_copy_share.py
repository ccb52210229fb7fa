"""Device time in ``copy`` ops (the whole-cache relayouts between the
scatter's and the kernel's layout) over device busy time, from the trace."""

SOURCE = "device_trace"
LAYER = "KV cache layout"
UNIT = "%"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy_s"]:
        return None
    copy = sum(s for name, s in t["device_ops"] if name.startswith("copy"))
    return 100.0 * copy / t["busy_s"]
