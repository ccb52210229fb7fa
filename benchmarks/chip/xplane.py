"""Reduction from a profiler trace (``.xplane.pb``) to device numbers.

    python -m benchmarks.chip.xplane <file.xplane.pb> <out.json>

``load_events`` reads the file with nothing but JAX (``ProfileData``) and
returns, per device plane, the op events and the program (module) events as
``(name, start_ns, dur_ns)``.  ``reduce_events`` is pure arithmetic on those
lists — checked in ``selfcheck.py`` on hand-made events and on a small
recorded trace:

  busy_s          union of the op intervals on a device, averaged over devices
  window_s        first op start to last op end (the traced steady window)
  device_ops      op name (trailing ``.N`` stripped) -> summed seconds, ranked
  programs        program name -> count, median and total of its device spans
  idle_gaps       the longest gaps between ops, labelled by the host span
                  (TraceMe on the host planes) that covers the gap's middle,
                  or "unattributed"
  collective_exposed_s  collective op time during which no other op ran on
                  that device
"""

from __future__ import annotations

import json
import re
import sys
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def load_events(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Event] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:") \
            or plane.name.startswith("/device:GPU:")
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
            if is_dev:
                d = devices.setdefault(plane.name, {"ops": [], "programs": [],
                                                    "lines": []})
                d["lines"].append(line.name)
                if line.name == "XLA Ops":
                    d["ops"] += evs
                elif line.name == "XLA Modules":
                    d["programs"] += evs
            elif plane.name.startswith("/host:"):
                host += [e for e in evs if e[2] > 0]
    return {"devices": devices, "host": host}


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _covered(iv: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in _union(iv))


def _base(name: str) -> str:
    """``%copy.494 = bf16[...] copy(...)`` (an op event carries its HLO
    text) and ``jit_window(4125...)`` (a program) both reduce to the bare
    name: ``copy``, ``jit_window``."""
    name = name.split(" = ", 1)[0].split("(", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def _median(v: list) -> float:
    v = sorted(v)
    n = len(v)
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


def reduce_events(ev: dict, top: int = 10) -> dict:
    busy, window, exposed = [], [], []
    ops: Dict[str, float] = {}
    programs: Dict[str, list] = {}
    gaps: List[Tuple[int, int, int]] = []
    n_dev = 0
    for _, d in sorted(ev["devices"].items()):
        if not d["ops"]:
            continue
        n_dev += 1
        iv = [(s, s + dur) for _, s, dur in d["ops"]]
        uni = _union(iv)
        busy.append(sum(b - a for a, b in uni))
        window.append(uni[-1][1] - uni[0][0])
        for (_, b0), (a1, _) in zip(uni, uni[1:]):
            gaps.append((a1 - b0, b0, a1))
        for nm, s, dur in d["ops"]:
            ops[_base(nm)] = ops.get(_base(nm), 0.0) + dur
        coll = [(s, s + dur) for nm, s, dur in d["ops"]
                if _base(nm).startswith(COLLECTIVES)]
        rest = [(s, s + dur) for nm, s, dur in d["ops"]
                if not _base(nm).startswith(COLLECTIVES)]
        # exposed = covered by collectives and by nothing else
        exposed.append(_covered(coll + rest) - _covered(rest))
        for nm, s, dur in d["programs"]:
            programs.setdefault(_base(nm), []).append(dur)
    if not n_dev:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0,
                "device_ops": [], "programs": {}, "idle_gaps": [],
                "collective_exposed_s": 0.0}
    host = sorted(ev["host"], key=lambda e: e[1])

    def label(mid: int) -> str:
        best = None
        for nm, s, dur in host:
            if s > mid:
                break
            if s + dur >= mid and (best is None or dur < best[1]):
                best = (nm, dur)          # innermost covering host span
        return best[0] if best else "unattributed"

    gaps.sort(reverse=True)
    n = max(1, n_dev)
    return {
        "devices": n_dev,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": sum(window) / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])][:50],
        "programs": {k: {"count": len(v), "median_ms": _median(v) / 1e6,
                         "total_s": sum(v) / n / 1e9}
                     for k, v in programs.items()},
        "idle_gaps": [[label((a + b) // 2), g / 1e9]
                      for g, a, b in gaps[:top]],
        "collective_exposed_s": sum(exposed) / n / 1e9,
    }


def main(argv) -> int:
    out = reduce_events(load_events(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
