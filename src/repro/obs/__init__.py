"""``repro.obs`` — always-available, off-by-default observability
(docs/OBSERVABILITY.md).

Three layers, one config:

* **Tracer** — structured spans/events on a dual timeline (simulated
  clock from ``repro.sim`` + host monotonic) for every upload,
  broadcast, window execution and its host phases, aggregation flush,
  eval and mid-round failure, tagged with client id, staleness, window
  size, codec and actual payload bytes.  Host spans also land in any
  ``jax.profiler`` trace as ``repro.<name>``, on the device's clock.
* **Metrics registry** — counters/gauges/histograms (window size,
  staleness, wire bytes, eval-cache hit rate, JIT compile count via
  ``jax.monitoring``) snapshot onto ``RunResult.metrics``; compile and
  persistent-cache totals from ``compile_stats()``.
* **Exporters** — JSONL trace, Chrome/Perfetto ``trace_event`` JSON
  (``chrome://tracing``-loadable), console run summary, and an opt-in
  ``jax.profiler`` hook around the batched engine's run.

Enable with ``FLRunConfig(obs=True)`` / ``Federation(obs=ObsConfig(
chrome_trace="run.json"))``; ``obs=None`` (the default) keeps every
hook site a dead branch — zero overhead, bit-exact either way.
"""
from repro.obs.compile_tracking import (compile_count, compile_secs,
                                       compile_stats, install)
from repro.obs.config import ObsConfig, resolve_obs
from repro.obs.exporters import read_jsonl
from repro.obs.metrics import MetricsRegistry, snapshot_percentile
from repro.obs.observer import Observer
from repro.obs.tracer import Tracer

__all__ = [
    "ObsConfig", "Observer", "Tracer", "MetricsRegistry", "resolve_obs",
    "snapshot_percentile", "compile_count", "compile_secs", "compile_stats",
    "install", "read_jsonl",
]
