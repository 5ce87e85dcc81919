"""Stack-wide observability: metrics registry, Chrome trace, provenance.

Three pieces, one enablement story:

  * :mod:`repro.obs.metrics` — the process-wide ``MetricsRegistry``
    (counters/gauges/histograms, Prometheus + stable-JSON exposition).
    ``metrics.enable()`` turns accounting on; disabled, every
    instrumented path is one ``is None`` check.
  * :mod:`repro.obs.trace` — the Chrome-trace ``TraceRecorder`` (grown
    out of ``serving.trace``, which re-exports it).  ``trace.install()``
    makes it the process-wide sink the compiler, executor and DSE
    drivers emit spans to, each on its own Perfetto process row; hand
    the same recorder to a fleet's ``trace=`` for one merged timeline.
    ``trace.span()`` is the one span entry point, and
    ``trace.use_profiler()`` adds the JAX profiler's trace as a second
    sink, on the device ops' clock.
  * :mod:`repro.obs.explain` — per-node compile provenance
    (``ExplainReport`` / ``explain_compile``; CLI in
    ``tools/explain.py``), fed by the :mod:`repro.obs.hooks` events
    the compiler tiers emit.

See ``docs/OBSERVABILITY.md`` for the operator guide.
"""
from . import hooks, metrics, trace                              # noqa: F401
from .metrics import MetricsRegistry                             # noqa: F401
from .trace import (TraceRecorder, load_trace,                   # noqa: F401
                    validate_chrome_trace)

__all__ = [
    "hooks", "metrics", "trace",
    "MetricsRegistry", "TraceRecorder",
    "load_trace", "validate_chrome_trace",
    "ExplainReport", "explain_compile",
]


def __getattr__(name):
    # ``explain`` imports the compiler (which imports this package), so
    # it loads lazily to keep the package import acyclic and light.
    if name in ("ExplainReport", "explain_compile", "explain"):
        import importlib
        explain = importlib.import_module(".explain", __name__)
        if name == "explain":
            return explain
        return getattr(explain, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
