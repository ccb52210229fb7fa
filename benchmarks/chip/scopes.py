"""Reduction from a profiler trace (``.xplane.pb``) to device time by the
program's own stage names (``dynamo_tpu.engine.model.SCOPES``).

    python -m benchmarks.chip.scopes <file.xplane.pb> <out.json>

A device op's ``op_name`` (``jit(window)/mlp/dot_general``) is not on the
event: on the TPU it is the ``tf_op`` stat of the event's *metadata*, which
``jax.profiler.ProfileData`` does not hand out.  So ``load_events`` reads the
protobuf's wire format itself (standard library only; the few message fields
it needs are listed below) and returns, per device plane, the op events as
``(name, start_ps, dur_ps, op_name)`` and the program events as ``(name,
start_ps, dur_ps)``.  ``reduce_events`` is pure arithmetic on those lists —
checked in ``selfcheck_scopes.py`` on hand-made events and on a small recorded
trace:

  busy_s      summed op time on a device, averaged over devices
  by_scope    scope -> seconds; ``unscoped`` holds the ops whose ``op_name``
              has no component of the vocabulary (mostly ops the compiler
              makes itself: async weight prefetches, ``copy-start/done``)
  coverage    1 - unscoped / busy
  unscoped_ops  the unscoped time by bare op name, ranked
  programs    program name -> its runs but the device's first and last (the
              trace's edges cut those), their median and mean span, and per
              run (a mean: it adds up to the mean span where the device has
              no gaps) the milliseconds by scope of the ops that started
              inside a run
"""

from __future__ import annotations

import json
import sys
from bisect import bisect_right
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks.chip.xplane import _base, _median

UNSCOPED = "unscoped"

# ---- the protobuf's wire format (tsl/profiler/protobuf/xplane.proto) -------
# XSpace: planes=1.  XPlane: name=2 lines=3 event_metadata=4 stat_metadata=5
# (maps: key=1 value=2).  XLine: name=2 timestamp_ns=3 events=4.
# XEvent: metadata_id=1 offset_ps=2 duration_ps=3.
# XEventMetadata: id=1 name=2 stats=5.  XStatMetadata: id=1 name=2.
# XStat: metadata_id=1 str_value=5 ref_value=7 (a stat_metadata id).


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a varint
    or a fixed width, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            ln, i = _varint(buf, i)
            yield field, wire, buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            yield field, wire, int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, memoryview]:
    key, val = 0, memoryview(b"")
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf) -> Optional[dict]:
    name, lines, ev_meta, stat_names = "", [], [], {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.append(v)
        elif f == 5:
            sid, sm = _map_entry(v)
            stat_names[sid] = next(
                (_text(x) for g, _, x in _fields(sm) if g == 2), "")
    if not (name.startswith("/device:TPU:")
            or name.startswith("/device:GPU:")):
        return None
    tf_op = {i for i, nm in stat_names.items() if nm == "tf_op"}
    meta: Dict[int, Tuple[str, str]] = {}
    for entry in ev_meta:
        mid, em = _map_entry(entry)
        ev_name = op_name = ""
        for f, _, v in _fields(em):
            if f == 2:
                ev_name = _text(v)
            elif f == 5:
                sid, sval = 0, ""
                for g, _, x in _fields(v):
                    if g == 1:
                        sid = x
                    elif g == 5:
                        sval = _text(x)
                    elif g == 7:
                        sval = stat_names.get(x, "")
                if sid in tf_op:
                    op_name = sval
        meta[mid] = (ev_name, op_name)
    out = {"ops": [], "programs": []}
    for ln in lines:
        lname, t0_ns, events = "", 0, []
        for f, _, v in _fields(ln):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        if lname not in ("XLA Ops", "XLA Modules"):
            continue
        for ev in events:
            mid = off = dur = 0
            for f, _, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            ev_name, op_name = meta.get(mid, ("", ""))
            start = t0_ns * 1000 + off
            if lname == "XLA Ops":
                out["ops"].append((ev_name, start, dur, op_name))
            else:
                out["programs"].append((ev_name, start, dur))
    return {"name": name, **out}


def load_events(path: str) -> dict:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices = {}
    for f, _, v in _fields(space):
        if f == 1:
            p = _plane(v)
            if p is not None:
                devices[p.pop("name")] = p
    return {"devices": devices}


# ---- arithmetic -------------------------------------------------------------


def scope_of(op_name: str, vocabulary) -> str:
    """The outermost component of ``op_name`` that is a stage name."""
    for part in op_name.split("/"):
        if part in vocabulary:
            return part
    return UNSCOPED


def reduce_events(ev: dict, vocabulary, top: int = 12) -> dict:
    vocabulary = tuple(vocabulary)
    by_scope: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    runs: Dict[str, List[Tuple[int, Dict[str, int]]]] = {}
    busy = 0
    n_dev = 0
    for _, d in sorted(ev["devices"].items()):
        if not d["ops"]:
            continue
        n_dev += 1
        # the trace's edges cut the first and the last run: not whole steps
        progs = sorted((s, s + dur, _base(nm))
                       for nm, s, dur in d["programs"])[1:-1]
        starts = [p[0] for p in progs]
        per_run: List[Dict[str, int]] = [{} for _ in progs]
        for nm, s, dur, op_name in d["ops"]:
            sc = scope_of(op_name, vocabulary)
            busy += dur
            by_scope[sc] = by_scope.get(sc, 0) + dur
            if sc == UNSCOPED:
                unscoped[_base(nm)] = unscoped.get(_base(nm), 0) + dur
            i = bisect_right(starts, s) - 1
            if i >= 0 and s < progs[i][1]:
                per_run[i][sc] = per_run[i].get(sc, 0) + dur
        for (s, e, nm), scopes in zip(progs, per_run):
            runs.setdefault(nm, []).append((e - s, scopes))
    if not n_dev:
        return {"devices": 0, "busy_s": 0.0, "by_scope": {},
                "coverage": None, "unscoped_ops": [], "programs": {}}
    programs = {}
    for nm, rr in runs.items():
        tot: Dict[str, int] = {}
        for _, scopes in rr:
            for sc, ps in scopes.items():
                tot[sc] = tot.get(sc, 0) + ps
        programs[nm] = {
            "runs": len(rr),
            "median_ms": _median([span for span, _ in rr]) / 1e9,
            "mean_ms": sum(span for span, _ in rr) / len(rr) / 1e9,
            "by_scope_ms": {sc: ps / len(rr) / 1e9
                            for sc, ps in sorted(tot.items())},
        }
    return {
        "devices": n_dev,
        "busy_s": busy / n_dev / 1e12,
        "by_scope": {sc: ps / n_dev / 1e12
                     for sc, ps in sorted(by_scope.items())},
        "coverage": 1.0 - by_scope.get(UNSCOPED, 0) / busy if busy else None,
        "unscoped_ops": [[k, v / n_dev / 1e12] for k, v in
                         sorted(unscoped.items(), key=lambda kv: -kv[1])][:top],
        "programs": programs,
    }


def program_scope_ms(summary: dict, word: str, scopes=None):
    """Milliseconds per run, over every program whose name holds ``word``,
    of the ops in ``scopes`` (None: all ops, the unscoped ones too)."""
    runs, total = 0, 0.0
    for name, p in (summary or {}).get("programs", {}).items():
        if word in name:
            runs += p["runs"]
            total += p["runs"] * sum(
                ms for sc, ms in p["by_scope_ms"].items()
                if scopes is None or sc in scopes)
    return total / runs if runs else None


def main(argv) -> int:
    from dynamo_tpu.engine.model import SCOPES

    out = reduce_events(load_events(argv[1]), SCOPES)
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
