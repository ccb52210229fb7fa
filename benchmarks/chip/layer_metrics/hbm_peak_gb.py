"""Peak bytes in use on the fullest chip, from memory_stats() through the worker /health probe, after the window."""

SOURCE = "program_counter"
LAYER = "device"
UNIT = "GB"
BETTER = "lower"
MOVES = "out_tok_s"


def read(ctx):
    peaks = [m["peak_bytes_in_use"] for m in ctx["health_end"]["memory"]
             if m.get("peak_bytes_in_use")]
    if not peaks or ctx["rehearse"]:
        return None
    return max(peaks) / 1e9
