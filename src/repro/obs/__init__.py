"""Serving-engine observability: metrics registry, step spans and the
request tracer.

- `repro.obs.metrics` — a lightweight in-process metrics registry
  (counters, gauges, bucketed histograms) that the engine, scheduler,
  paged KV cache, and fused sampler publish into; ``GET /metrics``
  renders it.  ``Engine.stats()`` remains as a thin compat view over it.
- `repro.obs.trace` — always on: each engine step and its phases
  (``schedule``, ``prefill``, ``decode``, ``sample``, ``commit``,
  ``cow``, ``spec_propose``, ``spec_verify``, and ``wait`` around every
  host block on the device) are ``jax.profiler`` annotations, so a
  profiler session lays them on its own clock over the device's
  operations; each step's account (a `Step`: phase self seconds, wait,
  host seconds, rows, live KV pages read, compiles) lands on the
  requests it advanced (``Request.steps``) and in ``engine.step_s`` /
  ``engine.step_host_s``.
  Cost: some tens of microseconds a step.  With ``Tracer(enabled=True)``
  it also records the steps, phases and per-request tracks (queued ->
  prefill chunks -> first token -> preempt/recompute -> finish) as
  Chrome trace-event JSON viewable in Perfetto
  (https://ui.perfetto.dev), its timestamps on the profiler's clock.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricView, MetricsRegistry,
    diff_snapshots)
from repro.obs.trace import ENGINE_PID, REQUEST_PID, Step, Tracer  # noqa: F401
