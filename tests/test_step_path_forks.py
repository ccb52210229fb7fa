"""The step path has the forks something other than a test can select, and
no others: every field of ``EngineConfig`` has a hand on it in the package
or stands in the list of the debts; dispatch buckets come from the config's
static grids (a stall-quarantined rung routes up); and a live seat the
scheduler skips for a round keeps its device state. CPU, single device."""

import ast
import dataclasses
import pathlib

import jax
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.scheduler import SchedSeq, SeqStatus

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "dynamo_tpu"

# Fields no module of the package sets (tests do): each a fork of the engine
# that only a test can select today, beside the ROADMAP item that decides
# whether it gets a hand on it (a flag, a cell) or goes.  A new field lands
# here, with its item, or a constructor in the package passes it.
NOTHING_SETS_YET = {
    "watermark": "Queue 3 item 10 (a constant the scheduler owns)",
    "enable_prefix_caching": "Queue 3 item 6 (one lookup a side; C5)",
    "attention_impl_spec": "Queue 3 item 4 (C2 decides it)",
    "attention_impl_prefill": "Queue 3 item 4 (C3 decides it)",
    "pipeline_depth": "Queue 3 items 2 and 11 (the two loops)",
    "sp_prefill_threshold": "Queue 3 item 5 (a long-context cell)",
    "spec_ngram_min": "Queue 3 item 10 (C2: the drafter's constants)",
    "spec_ngram_max": "Queue 3 item 10 (C2: the drafter's constants)",
    "spec_hist_cap": "Queue 3 item 10 (C2: derived from max_model_len)",
    "stall_seq_retries": "Queue 3 item 5 (fault recovery: a worker flag)",
    "stall_dead_threshold": "Queue 3 item 5 (fault recovery: a worker flag)",
    "pressure_spill_threshold": "Queue 3 item 5 (fault recovery)",
    "pressure_spec_threshold": "Queue 3 item 5 (fault recovery)",
    "pressure_shed_threshold": "Queue 3 item 5 (fault recovery)",
    "pressure_release": "Queue 3 item 5 (fault recovery)",
}


def _fields_the_package_sets():
    """Names passed by keyword to ``EngineConfig(...)`` or to a
    ``replace(...)`` of one anywhere in ``dynamo_tpu/`` but the file that
    declares them.  A ``**kwargs`` is not read: the simulated cluster's
    chaos scenarios (``mocker/cluster.py``, a test harness: ROADMAP Queue 3
    item 12) pass ``stall_*`` and ``pressure_*`` that way."""
    names = {f.name for f in dataclasses.fields(EngineConfig)}
    found = {}
    for path in sorted(PACKAGE.rglob("*.py")):
        if path == PACKAGE / "engine" / "config.py":
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            callee = getattr(fn, "id", None) or getattr(fn, "attr", None)
            if callee not in ("EngineConfig", "replace"):
                continue
            for kw in node.keywords:
                if kw.arg in names:
                    found.setdefault(kw.arg, f"{path.name}:{node.lineno}")
    return found


@pytest.fixture(scope="module")
def package_sets():
    return _fields_the_package_sets()


@pytest.mark.parametrize(
    "name", [f.name for f in dataclasses.fields(EngineConfig)])
def test_engine_option_has_a_hand_on_it_or_is_a_named_debt(
        name, package_sets):
    if name in NOTHING_SETS_YET:
        assert name not in package_sets, (
            f"{name} is set at {package_sets[name]}: take it off the list")
    else:
        assert name in package_sets, (
            f"no module of dynamo_tpu/ sets EngineConfig.{name}: only a "
            "test can select what it selects. Pass it from a constructor "
            "the program reaches, or list it in NOTHING_SETS_YET beside "
            "the ROADMAP item that will decide it")


def test_the_list_of_debts_names_fields_that_exist():
    names = {f.name for f in dataclasses.fields(EngineConfig)}
    assert set(NOTHING_SETS_YET) <= names
    assert len(names) == 35


# ------------------------- static buckets only ---------------------------

GRIDS = {"decode": (2, 4, 8), "prefill": (8, 16, 32)}


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(ModelConfig.tiny(), EngineConfig(
        num_blocks=32, max_model_len=64, max_num_batched_tokens=32,
        prefill_buckets=GRIDS["prefill"], decode_buckets=GRIDS["decode"],
        max_num_seqs=8, attention_impl="einsum"))


@pytest.mark.parametrize("kind", ["decode", "prefill"])
@pytest.mark.parametrize("quarantined, want", [
    # per rung of the grid: where one unit past the rung below it lands
    ((), (0, 1, 2)),            # the config's grid, nothing else
    ((1,), (0, 2, 2)),          # a wedged rung routes to the next one up
    ((0, 1), (2, 2, 2)),        # ... past every wedged rung
    ((2,), (0, 1, 2)),          # the top rung has nowhere to go: it stays
], ids=["none", "middle", "two", "top"])
def test_bucket_for_is_the_static_grid_and_routes_past_quarantine(
        engine, kind, quarantined, want):
    grid = GRIDS[kind]
    engine._shape_quarantine = {(kind, grid[i]) for i in quarantined}
    try:
        got = [engine._bucket_for(kind, n)
               for n in (1, grid[0] + 1, grid[1] + 1)]
        # a rung's own size lands where one unit less does
        assert got == [engine._bucket_for(kind, g) for g in grid]
        # the other kind's grid never sees this kind's quarantine
        other = "prefill" if kind == "decode" else "decode"
        assert ([engine._bucket_for(other, g) for g in GRIDS[other]]
                == list(GRIDS[other]))
    finally:
        engine._shape_quarantine = set()
    assert got == [grid[i] for i in want]
    assert engine._shape_bucket(kind, 1) == grid[0]


# ------------- a live seat skipped for a round under pool pressure -------

def test_a_live_seat_skipped_under_pool_pressure_keeps_its_device_state():
    """With windows in flight and the pool dry, a sequence that needs its
    next block is skipped for the round, neither preempted nor finished.
    The window dispatched without it leaves its seat's ``pos`` and
    ``last_tok`` on the device where the window before left them (it holds
    no column of the seat map, and at one step a window a seat at capacity
    advances nothing), so the round that schedules it again starts where
    the device stands."""
    eng = InferenceEngine(ModelConfig.tiny(), EngineConfig(
        block_size=4, num_blocks=16, max_model_len=32,
        max_num_batched_tokens=16, prefill_buckets=(16,),
        decode_buckets=(2,), max_num_seqs=2, attention_impl="einsum"))
    sched = eng.scheduler

    def seq(name, n):
        rng = np.random.default_rng(n)
        s = SchedSeq(seq_id=name, max_tokens=12, eos_token_ids=frozenset(),
                     prompt_ids=[int(t) for t in rng.integers(1, 250, n)])
        sched.add(s)
        return s

    def dispatch():
        batch = sched.schedule()
        eng._mark_preempted_seats(batch)
        return batch, eng._dispatch_batch(batch)

    def land(batch, handles):
        eng._postprocess(batch, eng._fetch_results(batch, handles))

    def device(s):
        ctl = jax.device_get(eng._ctl)
        return int(ctl["pos"][s.slot]), int(ctl["last_tok"][s.slot])

    # A's prompt ends one short of its block, B's in the middle of one
    a, b = seq("a", 3), seq("b", 5)
    land(*dispatch())
    assert [s.status for s in (a, b)] == [SeqStatus.RUNNING] * 2
    held = []
    while (bid := sched.pool.allocate()) is not None:
        held.append(bid)                    # the pool is dry

    first = dispatch()                      # in flight: A at 3, B at 5
    assert [r.seq for r in first[0].decode_rows] == [a, b]
    stood = device(a)
    assert stood[0] == 4                    # the end of A's only block
    second = dispatch()                     # A needs a block: skipped
    assert [r.seq for r in second[0].decode_rows] == [b]
    assert a.status is SeqStatus.RUNNING and not second[0].preempted
    assert a.slot not in eng._ap_cols
    assert device(a) == stood
    assert device(b)[0] == 7                # B went on

    land(*first)
    land(*second)
    assert a.output_ids[-1] == stood[1]     # the token the ring kept
    for bid in held:
        sched.pool.decref(bid)
    again, handles = dispatch()
    row = next(r for r in again.decode_rows if r.seq is a)
    assert (row.base, row.tok_src) == (stood[0], 0)
    assert row.tok_host == stood[1]
    assert device(a)[0] == stood[0] + 1
    land(again, handles)
    assert len(a.output_ids) == 3
