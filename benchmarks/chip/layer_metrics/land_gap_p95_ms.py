"""The gap tail at the first of three places, the landing: 95th percentile of
the ``t_land`` differences of consecutive decode records dispatched in the
window (StepStats JSONL, the fetch thread's clock), each weighted by the
later record's ``live_rows``: a row that decodes gets one token a landing, so
this is the gap every stream would see if nothing lay between the fetch
thread and its client: a decode step plus the prefill chunks run between two.
``sent_gap_p95_ms`` reads the same gaps at the worker's socket and
``itl_p95_ms`` at the client; the three populations differ a little (records
dispatched in the window; streams that ended in it, whole; events that arrived
in it), so they compare to a histogram bucket (19%), not to a percent.  A
reader only: the records exist since PR 24.  None where a decode record
carries more than one step (``decode_steps`` > 1, speculation: a landing is
then several tokens of a row) or fewer than two decode records landed."""

SOURCE = "program_counter"
LAYER = "engine loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p95_ms"


def read(ctx):
    dec = sorted((r for r in ctx["steps"] if r.get("kind") == "decode"
                  and r.get("t_land")), key=lambda r: r["t_land"])
    if len(dec) < 2 or any(r["padded_tokens"] > r["rows"] for r in dec):
        return None
    gaps = sorted(((b["t_land"] - a["t_land"]) * 1e3, b["live_rows"])
                  for a, b in zip(dec, dec[1:]))
    need = 0.95 * sum(w for _, w in gaps)
    seen = 0
    for gap, w in gaps:
        seen += w
        if seen >= need:
            return gap
    return None
