"""Traffic shapes: one general generator that reads a mix's parameter file.

The *shape* of a mix — how many requests, when each is due (open loop) or
which client sends it at which turn (closed loop), its prompt length, its
``max_tokens`` and its prefix group — is built from the file's parameters and
the file's own ``shape_seed`` and is therefore identical in every run.
Lengths are stratified: the n requests take the n mid-quantiles of the stated
distribution and are then shuffled by ``shape_seed``, so the offered totals do
not depend on a draw.  ``--seed`` draws token ids (``tokens_for``) and nothing
else: seed -> content only.

No JAX, no numpy-random state shared with anything: importable by the load
generator process.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import random
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TAIL_S = 1.0          # traffic goes on this long past the window's end


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


# ------------------------------ quantiles ----------------------------------


def _gamma_cdf(k: float, x: float) -> float:
    """Regularised lower incomplete gamma P(k, x) by its power series."""
    if x <= 0:
        return 0.0
    term = 1.0 / k
    total = term
    n = 0
    while abs(term) > 1e-15 * abs(total) and n < 10000:
        n += 1
        term *= x / (k + n)
        total += term
    return min(1.0, total * math.exp(-x + k * math.log(x) - math.lgamma(k)))


def quantile(dist: dict, q: float) -> float:
    """Inverse CDF of a distribution spec at q in (0, 1)."""
    kind = dist["dist"]
    if kind == "const":
        return float(dist["value"])
    if kind == "uniform":
        return dist["lo"] + q * (dist["hi"] - dist["lo"])
    if kind == "loguniform":
        a, b = math.log(dist["lo"]), math.log(dist["hi"])
        return math.exp(a + q * (b - a))
    if kind == "gamma":   # mean, cv -> shape k = 1/cv^2, scale = mean/k
        k = 1.0 / dist["cv"] ** 2
        scale = dist["mean"] / k
        lo, hi = 0.0, k + 40.0 * math.sqrt(k) + 40.0
        for _ in range(200):
            mid = 0.5 * (lo + hi)
            if _gamma_cdf(k, mid) < q:
                lo = mid
            else:
                hi = mid
        return 0.5 * (lo + hi) * scale
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, n: int, rnd: random.Random,
               integer: bool = True) -> list:
    """The n mid-quantiles of ``dist``, shuffled by ``rnd``."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        vals = [max(1, int(round(v))) for v in vals]
    rnd.shuffle(vals)
    return vals


def zipf_picks(n_items: int, s: float, n: int, rnd: random.Random) -> list:
    """n picks among ``n_items`` whose counts follow Zipf(s) by largest
    remainder (item 0 the most popular), in a ``rnd``-shuffled order."""
    w = [1.0 / (i + 1) ** s for i in range(n_items)]
    tot = sum(w)
    exact = [n * x / tot for x in w]
    counts = [int(e) for e in exact]
    order = sorted(range(n_items), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    picks = [i for i, c in enumerate(counts) for _ in range(c)]
    rnd.shuffle(picks)
    return picks


# ------------------------------ the shape ----------------------------------


def _scaled(dist: dict, scale: int) -> dict:
    if scale == 1:
        return dist
    out = dict(dist)
    for k in ("lo", "hi", "value"):
        if k in out:
            out[k] = max(2, out[k] / scale)
    return out


def build_shape(mix: dict, seconds: float,
                rate: Optional[float] = None, length_scale: int = 1,
                ramp_s: Optional[float] = None) -> dict:
    """The request list of one run.  ``length_scale`` > 1 divides every
    length (the CPU rehearsal's tiny model holds 512 positions)."""
    rnd = random.Random(int(mix["shape_seed"]))
    loop = mix["loop"]
    ramp = float(mix["ramp_s"] if ramp_s is None else ramp_s)
    horizon = ramp + seconds + TAIL_S
    if loop == "open":
        rate = float(rate if rate is not None else mix["rate"])
        n = int(math.ceil(rate * horizon))
        gap_dist = dict(mix["gap"], mean=1.0 / rate)
        gaps = stratified(gap_dist, n, rnd, integer=False)
        due, t = [], 0.0
        for g in gaps:
            due.append(t)
            t += g
        slots = [{"due": d} for d in due]
    else:
        clients = int(mix["clients"])
        per = int(mix["requests_per_client"])
        n = clients * per
        # turn-major: a block of consecutive slots is one turn of
        # neighbouring clients
        slots = [{"client": i % clients, "turn": i // clients}
                 for i in range(n)]
    plen = stratified(_scaled(mix["prompt_len"], length_scale), n, rnd)
    mtok = stratified(_scaled(mix["max_tokens"], length_scale), n, rnd)
    docs: List[int] = []
    group: List[Optional[int]] = [None] * n
    if mix.get("prefix"):
        p = mix["prefix"]
        docs = sorted(stratified(_scaled(p["doc_len"], length_scale),
                                 int(p["docs"]), rnd))
        rnd.shuffle(docs)
        group = zipf_picks(len(docs), float(p["zipf_s"]), n, rnd)
    work = [{"prompt_len": plen[i], "max_tokens": mtok[i], "group": group[i]}
            for i in range(n)]
    reqs = []
    for i, (s, w) in enumerate(zip(slots, work)):
        r = {"idx": i, **s, **w}
        # prompt_len is the fresh part; a grouped request carries its
        # document in front of it
        r["total_len"] = w["prompt_len"] + (
            docs[w["group"]] if w["group"] is not None else 0)
        reqs.append(r)
    shape = {"mix": mix["name"], "loop": loop, "ramp_s": ramp,
             "seconds": seconds, "horizon_s": horizon, "requests": reqs,
             "docs": docs, "rate": rate if loop == "open" else None,
             "clients": mix.get("clients"),
             "stagger_s": min(ramp, float(mix.get("stagger_s", ramp)))}
    shape["summary"] = summarize_shape(shape)
    return shape


def _stats(vals: list) -> dict:
    v = sorted(vals)
    n = len(v)
    return {"n": n, "sum": int(sum(v)), "mean": sum(v) / n,
            "min": v[0], "p50": v[n // 2], "p95": v[min(n - 1, int(0.95 * n))],
            "max": v[-1]}


def summarize_shape(shape: dict) -> dict:
    """Offered totals (open loop: everything due inside the window; closed
    loop: the per-client plans) — equal across seeds by construction."""
    reqs = shape["requests"]
    out = {"requests_in_plan": len(reqs),
           "prompt_len": _stats([r["total_len"] for r in reqs]),
           "max_tokens": _stats([r["max_tokens"] for r in reqs])}
    if shape["loop"] == "open":
        w0, w1 = shape["ramp_s"], shape["ramp_s"] + shape["seconds"]
        inw = [r for r in reqs if w0 <= r["due"] < w1]
        out["offered_in_window"] = {
            "requests": len(inw),
            "prompt_tokens": sum(r["total_len"] for r in inw),
            "output_tokens": sum(r["max_tokens"] for r in inw),
            "output_tok_s": sum(r["max_tokens"] for r in inw)
            / shape["seconds"]}
        gaps = [b["due"] - a["due"] for a, b in zip(reqs, reqs[1:])]
        m = sum(gaps) / len(gaps)
        out["gap_cv"] = (sum((g - m) ** 2 for g in gaps) / len(gaps)) ** .5 / m
    else:
        c = shape["clients"]
        per = {}
        for r in reqs:
            d = per.setdefault(r["client"], [0, 0])
            d[0] += r["total_len"]
            d[1] += r["max_tokens"]
        out["per_client_plan"] = {
            "clients": c, "turns": len(reqs) // c,
            "prompt_tokens_min_max": [min(v[0] for v in per.values()),
                                      max(v[0] for v in per.values())],
            "output_tokens_min_max": [min(v[1] for v in per.values()),
                                      max(v[1] for v in per.values())]}
        # a digest of (client, turn) -> lengths: equal iff the plans are
        h = hashlib.sha256(json.dumps(
            [(r["client"], r["turn"], r["total_len"], r["max_tokens"],
              r["group"]) for r in reqs]).encode()).hexdigest()[:16]
        out["plan_digest"] = h
    if shape["docs"]:
        out["docs"] = _stats(shape["docs"])
        out["doc_pool_blocks"] = sum(-(-d // 16) for d in shape["docs"])
    return out


# ------------------------------ token ids ----------------------------------


def tokens_for(seed: int, kind: str, idx: int, n: int, vocab: int) -> list:
    """``n`` token ids for item ``idx`` of ``kind`` under ``--seed``; ids
    avoid the first 256 (byte tokens, some of which detokenise to nothing)."""
    tag = {"req": 1, "doc": 2, "warm": 3, "ref": 4}[kind]
    rng = np.random.default_rng([int(seed), tag, int(idx)])
    return rng.integers(256, vocab, size=n).tolist()


def request_tokens(shape: dict, r: dict, seed: int, vocab: int,
                   lap: int = 0) -> list:
    """The prompt of request ``r`` in a client's ``lap``-th walk through its
    plan (closed loop).  A lap's fresh tokens are those of an index no other
    lap has, so a lapped prompt repeats nothing but its document."""
    fresh = tokens_for(seed, "req", r["idx"] + lap * len(shape["requests"]),
                       r["prompt_len"], vocab)
    if r["group"] is None:
        return fresh
    return doc_tokens(shape, r["group"], seed, vocab) + fresh


def doc_tokens(shape: dict, d: int, seed: int, vocab: int) -> list:
    return tokens_for(seed, "doc", d, shape["docs"][d], vocab)


# ------------------------- reachable step programs --------------------------


def _pow2(n: int, cap: Optional[int] = None) -> int:
    b = 1
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def reachable_prefill_programs(shape: dict, eng: dict) -> list:
    """Every (T, W) prefill program this shape can make the scheduler run,
    from the engine's own rules (``Scheduler.schedule`` / ``_prefill_arrays``):
    the first prefill of a step gets ``max_batched_tokens`` less the live
    decode rows, a later one is never fragmented; T is the chunk's prefill
    bucket and W the power-of-two bucket of the blocks up to the chunk's end.
    A grouped request may start anywhere in its document (a partial hit)."""
    bs, budget = eng["block_size"], eng["max_batched_tokens"]
    buckets, cap = eng["prefill_buckets"], max(eng["prefill_buckets"])
    lo_cut = max(1, min(budget, cap) - eng["max_num_seqs"])
    hi_cut = min(budget, cap)
    wcap = -(-eng["max_model_len"] // bs)
    out = set()
    seen = set()
    for r in shape["requests"]:
        L = r["total_len"]
        hit_hi = 0
        if r["group"] is not None:
            hit_hi = (shape["docs"][r["group"]] // bs) * bs
        if (L, hit_hi) in seen:
            continue
        seen.add((L, hit_hi))
        plo, phi = 0, hit_hi
        while plo < L:
            # a final chunk: remaining fits one chunk
            r_lo, r_hi = max(1, L - phi), min(hi_cut, L - plo)
            if r_lo <= r_hi:
                W = _pow2(-(-L // bs), wcap)
                for b in buckets:
                    prev = max([x for x in buckets if x < b], default=0)
                    if r_lo <= b and r_hi > prev:
                        out.add((b, W))
            # a cut chunk: remaining exceeds what the step grants
            if L - plo > lo_cut:
                e_lo, e_hi = plo + lo_cut, min(phi + hi_cut, L - 1)
                T = _bucket(lo_cut, buckets)
                for W in {_pow2(-(-e // bs), wcap)
                          for e in range(e_lo, e_hi + 1, 1)}:
                    out.add((T, W))
                    out.add((_bucket(hi_cut, buckets), W))
            plo, phi = plo + lo_cut, phi + hi_cut
    return sorted(out)


def reachable_decode_buckets(shape: dict, eng: dict) -> list:
    top = eng["max_num_seqs"]
    if shape["loop"] == "closed":
        top = min(top, shape["clients"])
    b = _bucket(top, eng["decode_buckets"])
    return [x for x in eng["decode_buckets"] if x <= b]
