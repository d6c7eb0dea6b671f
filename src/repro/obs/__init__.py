"""Serving observability: span tracing + metrics + kernel profiling hooks.

One `Observability` bundle threads through the serving pipeline
(`RequestQueue` / `ContinuousBatchingScheduler` / `ExecutionBackend` /
`VerifierCascade` / `ControlLoop`): components take ``obs=None`` and fall
back to `NULL_OBS`, whose `NullTracer`/`NullRegistry` make every
instrumentation site a guarded no-op — serving output is bit-identical and
overhead is gated <5% on ``benchmarks/serving_schedule.py`` with the full
stack on.

    from repro.obs import make_observability
    obs = make_observability()                 # live tracer + registry
    sched = ContinuousBatchingScheduler(backend, router, cfg, obs=obs)
    ...
    obs.metrics.write("metrics.json")          # + metrics.prom sibling
    obs.tracer.save("spans.jsonl")             # TraceStore-compatible

This package is dependency-light by design: metrics are pure
python/stdlib; a live `Tracer` and `profiling` import jax when used.
"""
from repro.obs.metrics import (Counter, DEFAULT_BUCKETS, Gauge, Histogram,
                               MetricsRegistry, NullRegistry,
                               PeriodicReporter)
from repro.obs.profiling import annotate, kernel_scope, tpu_roofline_us
from repro.obs.tracer import (LIFECYCLE, NullTracer, Span, Tracer,
                              lifecycles_complete, reconstruct_lifecycles)


class Observability:
    """The bundle components thread: a tracer and a metrics registry.
    ``enabled`` is True when either side is live."""

    def __init__(self, tracer, metrics):
        self.tracer = tracer
        self.metrics = metrics

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled or self.metrics.enabled


#: shared disabled bundle — the default for every ``obs=None`` component
NULL_OBS = Observability(NullTracer(), NullRegistry())


def make_observability(store=None) -> Observability:
    """A live bundle: fresh `Tracer` (optionally mirroring spans into a
    `TraceStore`) + fresh `MetricsRegistry`."""
    return Observability(Tracer(store=store), MetricsRegistry())


__all__ = [
    "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram", "LIFECYCLE",
    "MetricsRegistry", "NULL_OBS", "NullRegistry", "NullTracer",
    "Observability", "PeriodicReporter", "Span", "Tracer", "annotate",
    "kernel_scope", "lifecycles_complete", "make_observability",
    "reconstruct_lifecycles", "tpu_roofline_us",
]
