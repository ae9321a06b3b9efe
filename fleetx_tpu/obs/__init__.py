"""Unified observability layer: metrics registry, span tracing,
structured events, HTTP exposition.

The substrate every subsystem reports through (docs/OBSERVABILITY.md):

- :mod:`fleetx_tpu.obs.registry` — process-wide Counter/Gauge/Histogram
  families with labels and bounded percentile reservoirs; Prometheus
  text + JSON snapshot expositions.
- :mod:`fleetx_tpu.obs.tracing` — nested host spans in a ring buffer
  (each with its ``parent``), Chrome-trace export, and a
  ``jax.profiler.TraceAnnotation`` bridge (always on) that puts the host
  phases on the device trace's clock: leaf spans name every host phase
  and every blocking fetch of ``ServingEngine.step()`` and
  ``Trainer.fit()``, dispatch spans apart from wait spans.
- :mod:`fleetx_tpu.obs.events` — bounded log of typed operational
  events (sentry skips, quarantines, recoveries, shutdowns), asserted
  on by the chaos suite.
- :mod:`fleetx_tpu.obs.http` — stdlib daemon-thread server: ``GET
  /metrics`` ``/snapshot`` ``/trace`` ``/healthz`` (drain-aware),
  enabled by ``FLEETX_OBS_PORT``.

Everything here is host-side and read-only with respect to the data
path: the serving byte-parity suites run with instrumentation enabled.

    from fleetx_tpu.obs import emit, get_registry, span

    ticks = get_registry().counter(TICKS_METRIC)  # a "fleetx_*" literal —
    with span("serving.tick"):                    # snake_case, fleetx_
        ticks.inc()                               # prefix, and a row in
    emit("engine_recovery", number=1)             # docs/OBSERVABILITY.md
                                                  # (lint-enforced)
"""

from fleetx_tpu.obs.events import Event, EventLog, emit, get_event_log
from fleetx_tpu.obs.http import (
    ObsServer,
    get_server,
    health_report,
    health_status,
    healthz_payload,
    maybe_start_from_env,
    register_health,
    unregister_health,
)
from fleetx_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from fleetx_tpu.obs.tracing import Span, SpanRecorder, get_recorder, span

__all__ = [
    "Counter",
    "Event",
    "EventLog",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "ObsServer",
    "Span",
    "SpanRecorder",
    "emit",
    "get_event_log",
    "get_recorder",
    "get_registry",
    "get_server",
    "health_report",
    "health_status",
    "healthz_payload",
    "maybe_start_from_env",
    "register_health",
    "span",
    "unregister_health",
]
