"""The gap tail at the second of three places, the worker's socket: the
``sent_gaps`` histograms (gaps between a stream's consecutive data-frame send
completions, ``IngressServer``) of the ``worker.ingress`` spans that ended in
the window, added bucket by bucket; the value is the upper edge of the bucket
that holds the 95th percentile (buckets a factor 2^(1/4) apart: read it to
19%).  Over ``land_gap_p95_ms`` it adds the worker's event loop and the
transport's write; ``itl_p95_ms`` at the client adds the frontend and SSE.
Whole streams that ENDED in the window, so gaps from before it count and the
streams still running do not.  None where the spans carry no histogram."""

SOURCE = "program_span"
LAYER = "worker stream-out"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p95_ms"


def read(ctx):
    w0, w1 = ctx["window"]
    total, lo, ratio = None, None, None
    for s in ctx["spans"]:
        h = (s.get("attrs") or {}).get("sent_gaps")
        end = s.get("end_mono")
        if (s.get("name") != "worker.ingress" or not h or end is None
                or not w0 <= end < w1):
            continue
        if total is None:
            total, lo, ratio = list(h["counts"]), h["lo_s"], h["ratio"]
        elif (h["lo_s"], h["ratio"], len(h["counts"])) == (lo, ratio,
                                                           len(total)):
            total = [a + b for a, b in zip(total, h["counts"])]
    if not total or not sum(total):
        return None
    need = 0.95 * sum(total)
    seen = 0
    for i, c in enumerate(total):
        seen += c
        if seen >= need and c:
            # bucket i ends at lo * ratio**i; the last one has no end
            return 1e3 * lo * ratio ** min(i, len(total) - 2)
    return None
