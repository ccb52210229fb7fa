"""(Cells whose users feel TTFT.)  Share of the traced window in which no op ran on the device: 1 - union of
op intervals / window.  The configuration runs half the model's layers, so
host work is a larger share here than in a deployment."""

SOURCE = "device_trace"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "ttft_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics._common import idle_share

    return idle_share(ctx["trace"])
