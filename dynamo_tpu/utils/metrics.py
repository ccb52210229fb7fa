"""Hierarchical Prometheus metrics registries.

Namespace/component/endpoint-scoped metric factories with automatic labels,
equivalent to the reference's ``MetricsRegistry`` trait hierarchy
(ref: lib/runtime/src/metrics.rs:365, metrics/prometheus_names.rs). Backed by
``prometheus_client``; each scope shares one process ``CollectorRegistry`` and
prefixes metric names + injects scope labels.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client.core import CounterMetricFamily

# Frequency buckets tuned for LLM serving latencies (TTFT/ITL in seconds),
# same role as the reference's http/service/metrics.rs histograms.
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)


class MetricsRegistry:
    """A scope (runtime / namespace / component / endpoint) that mints metrics.

    Child scopes share the root ``CollectorRegistry`` and accumulate constant
    labels, mirroring the reference's auto-labelled hierarchy.
    """

    def __init__(
        self,
        registry: Optional[CollectorRegistry] = None,
        prefix: str = "dynamo",
        const_labels: Optional[Dict[str, str]] = None,
    ):
        self.registry = registry or CollectorRegistry()
        self.prefix = prefix
        self.const_labels = dict(const_labels or {})
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}

    def child(self, **labels: str) -> "MetricsRegistry":
        merged = dict(self.const_labels)
        merged.update(labels)
        sub = MetricsRegistry(self.registry, self.prefix, merged)
        sub._metrics = self._metrics  # share the mint cache across scopes
        sub._lock = self._lock
        return sub

    def _full_name(self, name: str) -> str:
        return f"{self.prefix}_{name}"

    def _get_or_create(self, cls, name: str, doc: str, labelnames, **kwargs):
        key = self._full_name(name)
        with self._lock:
            metric = self._metrics.get(key)
            if metric is None:
                metric = cls(
                    key,
                    doc,
                    labelnames=tuple(labelnames),
                    registry=self.registry,
                    **kwargs,
                )
                self._metrics[key] = metric
        return metric

    def _labelnames(self, extra: Sequence[str]) -> tuple:
        return tuple(self.const_labels.keys()) + tuple(extra)

    def _bind(self, metric, extra_labels: Sequence[str]):
        if extra_labels:
            return _Bound(metric, self.const_labels)
        # a metric with no labels at all cannot take .labels()
        return metric.labels(**self.const_labels) if self.const_labels else metric

    def counter(self, name: str, doc: str, extra_labels: Sequence[str] = ()):
        c = self._get_or_create(Counter, name, doc, self._labelnames(extra_labels))
        return self._bind(c, extra_labels)

    def gauge(self, name: str, doc: str, extra_labels: Sequence[str] = ()):
        g = self._get_or_create(Gauge, name, doc, self._labelnames(extra_labels))
        return self._bind(g, extra_labels)

    def histogram(
        self, name: str, doc: str, extra_labels: Sequence[str] = (), buckets=LATENCY_BUCKETS
    ):
        h = self._get_or_create(
            Histogram, name, doc, self._labelnames(extra_labels), buckets=buckets
        )
        return self._bind(h, extra_labels)

    def counter_fn(self, name: str, doc: str, fn) -> None:
        """A counter whose value is ``fn()`` at scrape time: for a total
        some other object already keeps (idempotent per name)."""
        key = self._full_name(name)
        with self._lock:
            if key not in self._metrics:
                self._metrics[key] = _FnCounter(key, doc, fn,
                                                self.const_labels)
                self.registry.register(self._metrics[key])

    def render(self) -> bytes:
        """Prometheus text exposition of every metric in this process scope."""
        return generate_latest(self.registry)


def validate_exposition(body: bytes) -> list:
    """Round-trip a text-exposition payload through the reference parser
    and return the parsed sample tuples.

    Raises ``ValueError`` on any conformance violation (unescaped label
    values or HELP text, malformed sample lines, duplicate series) — the
    scrape-and-validate test runs every registry through this, so nasty
    label values (newlines, quotes, backslashes) can't silently corrupt
    the exposition.
    """
    from prometheus_client.parser import text_string_to_metric_families

    samples = []
    seen = set()
    for fam in text_string_to_metric_families(body.decode("utf-8")):
        for s in fam.samples:
            key = (s.name, tuple(sorted(s.labels.items())))
            if key in seen:
                raise ValueError(f"duplicate series {key!r}")
            seen.add(key)
            samples.append(s)
    return samples


class _FnCounter:
    """Collector behind :meth:`MetricsRegistry.counter_fn`."""

    def __init__(self, name: str, doc: str, fn, const_labels: Dict[str, str]):
        self._name, self._doc, self._fn = name, doc, fn
        self._labels = dict(const_labels)

    def collect(self):
        fam = CounterMetricFamily(self._name, self._doc,
                                  labels=list(self._labels))
        fam.add_metric(list(self._labels.values()), float(self._fn()))
        yield fam


class _Bound:
    """Partially-bound metric: const labels applied, extra labels at call time."""

    def __init__(self, metric, const_labels: Dict[str, str]):
        self._metric = metric
        self._const = const_labels

    def labels(self, **extra: str):
        merged = dict(self._const)
        merged.update(extra)
        return self._metric.labels(**merged)

    def remove(self, **extra: str) -> None:
        """Drop one label-set's child series (e.g. a departed worker's
        gauges) so stale values stop being scraped. No-op if the label set
        was never observed."""
        merged = dict(self._const)
        merged.update(extra)
        try:
            values = [merged[n] for n in self._metric._labelnames]
            self._metric.remove(*values)
        except KeyError:
            pass
