"""Engine flight recorder: live MFU/goodput accounting, compile-and-remat
watchdog, on-demand TPU profiles.

Layout:

* :mod:`.flops` — the ONE analytic FLOPs/parameter model (attention term
  included)
* :mod:`.stepstats` — per-engine-step records + windowed live gauges
* :mod:`.compilewatch` — per-jitted-function XLA recompile counters and
  ``[SPMD]`` involuntary-remat parsing
* :mod:`.profiling` — ``/debug/profile?ms=N`` jax.profiler capture
* :mod:`.gauges` — worker-local ``engine_*`` Prometheus gauges
* :mod:`.report` — ``python -m dynamo_tpu.observability`` JSONL report

Nothing here imports jax at module scope except via the engine's own lazy
paths, so control-plane processes (frontend, aggregator, planner) can use
the package without paying a backend import.
"""

from .flops import FlopsModel, active_param_count, param_count, peak_flops
from .stepstats import StepRecord, StepStats

__all__ = [
    "FlopsModel",
    "StepRecord",
    "StepStats",
    "active_param_count",
    "param_count",
    "peak_flops",
]
