"""repro.obs — tracing and telemetry for the serving/solver stack.

Three surfaces (docs/observability.md is the usage guide):

* SPANS — ``span(name)`` is the one instrumentation call: it records
  into the ambient ``Tracer`` (``use_tracer``) and, while
  a ``jax.profiler`` capture runs, annotates the capture's host plane on
  the device trace's clock (``step_annotation``).  The engines and the
  batch front end emit per-request lifecycle spans (submit/validate/
  queue-wait/bucket-pad/batch-stage/device-solve/solve-dispatch/crop/wait/
  cache-put/refill-admission/resolve), and ``watch_compiles`` adds
  ``compile`` spans; export with ``Tracer.save`` (Chrome trace,
  Perfetto-loadable) or read ``Tracer.spans()`` directly.  Install
  ambiently with ``use_tracer``; the engines take ``tracer=`` (default:
  the ambient one at construction) and run each stage under it.
* CYCLE EVENTS — ``repro.core.solver_loop.cycle_events`` streams
  structured per-cycle telemetry (live counts, rounds, heuristic
  invocations, compaction gathers) from both solver-loop drivers.
* METRICS EXPORT — ``prometheus_text`` renders a ``SchedulerMetrics``
  snapshot in the Prometheus text exposition format.

Disabled observability is cheap by construction: a span with no tracer
and no capture is one contextvar read and one C call (plus one more
contextvar read for the engine's ``use_tracer`` per stage), and results are
bit-identical with tracing on or off (tests/test_obs.py).
"""
from repro.obs.export import prometheus_text
from repro.obs.trace import (Span, Tracer, current_tracer, load_trace, span,
                             step_annotation, use_tracer, watch_compiles)

__all__ = [
    "Span",
    "Tracer",
    "current_tracer",
    "load_trace",
    "prometheus_text",
    "span",
    "step_annotation",
    "use_tracer",
    "watch_compiles",
]
