"""Worker-local Prometheus gauges for the engine flight recorder.

``EngineObsGauges`` mints the ``engine_*`` gauges on a MetricsRegistry and
refreshes them from ``engine.obs_snapshot()``; ``refresh()`` doubles as the
``WorkerMetricsPublisher.obs_fn`` so the same snapshot rides the wire to
the metrics aggregator (per-worker gauges + planner signals) at the
publish cadence — one read of the recorder per interval, zero per-token
overhead.
"""

from __future__ import annotations

from typing import Dict


class EngineObsGauges:
    def __init__(self, registry, engine):
        self._engine = engine
        # MFU needs the device's published peak: where there is none (any
        # non-TPU platform) the gauges do not exist — absent, not zero
        self._g_mfu = self._g_mfu_class = None
        obs = getattr(engine, "obs", None)
        if obs is not None and obs.peak_flops:
            self._g_mfu = registry.gauge(
                "engine_mfu",
                "live model-FLOPs utilization over the trailing window "
                "(goodput FLOPs / peak; attention term included)",
            )
            self._g_mfu_class = registry.gauge(
                "engine_mfu_by_class",
                "live MFU split by step class", ["step"]
            )
        self._g_goodput = registry.gauge(
            "engine_goodput_tok_s",
            "real tokens landed per second over the trailing window",
        )
        self._g_host_busy = registry.gauge(
            "engine_host_busy_ratio",
            "share of the trailing window the engine-loop task was busy "
            "(StepRecord.host_s sums): what a faster device would uncover",
        )
        self._g_loop_busy = registry.gauge(
            "engine_event_loop_busy_ratio",
            "share of the trailing window the worker's whole event loop was "
            "busy (StepRecord.loop_busy_s sums): the engine-loop task, every "
            "stream's way out, and the loop thread's waits for the "
            "interpreter lock; near 1 the loop, not the device, paces the "
            "tokens",
        )
        self._g_pad_waste = registry.gauge(
            "engine_padding_waste_ratio",
            "fraction of dispatched FLOPs burnt on bucket padding",
        )
        self._g_waste = registry.gauge(
            "engine_wasted_flops_ratio",
            "fraction of dispatched FLOPs wasted, by cause", ["cause"]
        )
        self._g_recompiles = registry.gauge(
            "engine_recompiles_total",
            "steady-state XLA backend compiles per jitted function "
            "(anything nonzero after warmup is a shape leak)", ["fn"]
        )
        self._g_remats = registry.gauge(
            "engine_involuntary_remats_total",
            "XLA [SPMD] involuntary full rematerialization warnings seen",
        )

    def refresh(self) -> Dict[str, float]:
        """Pull one recorder snapshot, set every gauge, return the wire
        dict for the load-metrics publisher."""
        snap = self._engine.obs_snapshot()
        if not snap:
            return {}
        if self._g_mfu is not None:
            self._g_mfu.set(snap["mfu"])
            self._g_mfu_class.labels(step="prefill").set(snap["mfu_prefill"])
            self._g_mfu_class.labels(step="decode").set(snap["mfu_decode"])
        self._g_goodput.set(snap.get("goodput_tok_s", 0.0))
        self._g_host_busy.set(snap.get("host_busy_ratio", 0.0))
        self._g_loop_busy.set(snap.get("loop_busy_ratio", 0.0))
        self._g_pad_waste.set(snap.get("padding_waste_ratio", 0.0))
        self._g_waste.labels(cause="padding").set(
            snap.get("padding_waste_ratio", 0.0))
        self._g_waste.labels(cause="spec_reject").set(
            snap.get("spec_reject_waste_ratio", 0.0))
        for fn, n in (snap.get("recompiles_by_fn") or {}).items():
            self._g_recompiles.labels(fn=fn).set(n)
        self._g_remats.set(snap.get("involuntary_remats_total", 0))
        # the wire snapshot carries scalars only (msgpack-friendly, and the
        # aggregator's zero-default reads stay flat)
        return {
            k: v for k, v in snap.items()
            if isinstance(v, (int, float))
        }
