"""Host milliseconds a decode step costs on the three threads that serve it:
median over the window's decode records of ``host_s + dispatch_s + unpack_s``
(event-loop task busy since the last handoff, dispatch thread inside
``_dispatch_decode``, fetch thread from the ``device_get``'s return to commit).
Serial if the device were infinitely fast; today hidden behind it."""

SOURCE = "program_counter"
LAYER = "engine loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.metrics import percentile

    ms = [1e3 * (r["host_s"] + r["dispatch_s"] + r["unpack_s"])
          for r in ctx["steps"]
          if r.get("kind") == "decode" and "host_s" in r]
    return percentile(ms, 50) if ms else None
