"""Seats of the state pool that a decode step advances, over the seats the
engine has (``--max-num-seqs``): StepStats ``state_rows`` over the steps of
a decode record, averaged over the window's decode records.  A guard
beside ``batch_occupancy``: the state pool's memory is sized by the seats,
and a pool that runs half empty was memory a longer cache could have had.
A program without the counter gives nothing."""

SOURCE = "program_counter"
LAYER = "state pool"
UNIT = "%"
BETTER = "higher"
MOVES = "tpot_p50_ms"


def read(ctx):
    from benchmarks.chip.layer_metrics import _kda

    recs = _kda.records(ctx, "decode", "state_rows")
    seats = ctx["engine"]["max_num_seqs"]
    if not recs or not seats:
        return None
    # state_rows = live rows x the window's steps (padded_tokens / rows)
    per_step = [r["state_rows"] / max(1, r["padded_tokens"] // r["rows"])
                for r in recs]
    return 100.0 * sum(per_step) / len(per_step) / seats
