"""Observability plane over the LCAP stream.

Four layers, each owning a different kind of signal:

- :mod:`repro.obs.registry` — typed internal metrics (counter / gauge /
  histogram) that the proxy, cluster, ack tracker, and transport publish
  into.  These describe the *fabric*: dispatch latency, outbox depth,
  backpressure parks, redeliveries.
- :mod:`repro.obs.spans` — the program's spans: one process-wide ring
  recorder (``TRACER``) on ``perf_counter_ns``, written where the work
  happens (journal, route, dispatch, queue waits, sessions, the train
  step) and annotated into the profiler's trace as ``repro.<span>``.
- :mod:`repro.obs.aggregator` — a windowed aggregation consumer that
  folds the *stream itself* into per-(op, jobid, producer, shard)
  tumbling windows with sliding views and trend deltas.
- :mod:`repro.obs.exporter` / :mod:`repro.obs.dashboard` — the edges:
  a Prometheus-text HTTP endpoint, a Ganglia-shaped pusher, and a
  ``top``-style terminal view.

The core imports ``spans`` at module level; the aggregator and the
edges load on first use, since the aggregator is itself a consumer of
the core.
"""

from repro.obs.registry import (          # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, merge_snapshots,
)
from repro.obs.spans import TRACER, RingOverwritten   # noqa: F401

_LAZY = {
    "ActivityAggregator": "repro.obs.aggregator",
    "PrometheusExporter": "repro.obs.exporter",
    "GangliaPusher": "repro.obs.exporter",
    "render_prometheus": "repro.obs.exporter",
    "ActivityTop": "repro.obs.dashboard",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)
