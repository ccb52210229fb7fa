"""Percentile arithmetic and the reduction from the load generator's records
to the numbers a user feels.  All times are the client's clock
(``time.monotonic`` of the load generator, which the harness shares: one
machine, one CLOCK_MONOTONIC).

A sample belongs to the window by the time of its event: a first token by
when it arrived, a gap by when it ended, a completion by its last token.
"""

from __future__ import annotations

from typing import List, Optional


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def request_ok(r: dict) -> bool:
    """200, ended with [DONE], exactly max_tokens tokens, no error event."""
    return (r.get("status") == 200 and r.get("done") and not r.get("errors")
            and r.get("completion_tokens") == r["max_tokens"])


def reduce_client(records: List[dict], w0: float, w1: float, chips: int,
                  open_loop: bool) -> dict:
    """End-to-end and client-view numbers over the window [w0, w1)."""
    ttft, tpot, gaps, lag, times = [], [], [], [], []
    tokens_in = 0
    done_in, attempted, failed = 0, 0, 0
    for r in records:
        ev = r.get("events") or []       # [[t, n_tokens], ...]
        t_ref = r["due_t"] if open_loop else r["send_t"]
        touches = (w0 <= r["send_t"] < w1) or any(
            w0 <= t < w1 for t, _ in ev)
        finished_in = bool(r.get("end_t")) and w0 <= r["end_t"] < w1
        if r.get("cut"):
            # still streaming when the generator stopped: judged on what
            # arrived (a stream cut by us is not a failure of the server)
            bad = bool(r.get("errors")) or r.get("status") not in (200, None)
        else:
            bad = not request_ok(r)
        if touches or finished_in:
            attempted += 1
            failed += 1 if bad else 0
        if w0 <= r["send_t"] < w1:
            lag.append((r["send_t"] - r["due_t"]) * 1e3)
        for i, (t, n) in enumerate(ev):
            if w0 <= t < w1:
                tokens_in += n
                times.append(t)
                if i == 0:
                    ttft.append((t - t_ref) * 1e3)
                else:
                    gaps.append((t - ev[i - 1][0]) * 1e3)
        if finished_in and not bad and len(ev) > 1:
            done_in += 1
            n_tok = sum(n for _, n in ev)
            if n_tok > 1:
                tpot.append((ev[-1][0] - ev[0][0]) * 1e3 / (n_tok - 1))
    secs = w1 - w0
    times.sort()
    # the longest stretch of the window in which no stream got a token: a
    # stall of the whole system, which no median and no p95 of gaps shows
    silence = max((b - a for a, b in zip([w0] + times, times + [w1])),
                  default=None)
    return {
        "longest_silence_ms": None if silence is None else silence * 1e3,
        "attempted": attempted, "failed": failed, "completed": done_in,
        "tokens_in_window": tokens_in,
        "out_tok_s": tokens_in / secs / chips,
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p95_ms": percentile(ttft, 95),
        "tpot_p50_ms": percentile(tpot, 50),
        "itl_p95_ms": percentile(gaps, 95),
        "itl_p99_ms": percentile(gaps, 99),
        "gen_lag_p99_ms": percentile(lag, 99),
        "n_ttft": len(ttft), "n_tpot": len(tpot), "n_gaps": len(gaps),
    }
