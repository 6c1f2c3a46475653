"""ytklearn_tpu.obs — unified tracing/metrics subsystem.

Public surface (see docs/observability.md):

  span(name, settle=None, step=None, **attrs)
                                     nested wall-clock span (ctx manager)
                                     with id, parent id and step; also a
                                     jax.profiler TraceAnnotation
  root_span(name, **attrs)           the run's root (`train.run`), opened
                                     once however many layers ask for it
  step_span(name, step, **attrs)     a span that is a step of the loop
                                     (StepTraceAnnotation in a device trace)
  spans_between(t0, t1)              finished spans of an interval given on
                                     time.perf_counter
  scopes                             names of the program's own on the
                                     device: named scopes, AOT programs and
                                     the instruction -> scope map
  inc(name, value=1.0)               counter add
  gauge(name, value)                 gauge set
  event(name, **attrs)               instant trace marker
  heartbeat(name, every_s=30)        rate-limited structured progress logger
  enabled() / configure(...)         state; YTK_TRACE / YTK_OBS env knobs
  snapshot() / reset()               registry access
  flush()                            write configured exports now
  export_chrome_trace / export_jsonl / load_jsonl

Run-health layer (obs/health.py, obs/recorder.py — docs/observability.md):

  health                             NaN/divergence/ingest/tree sentinels,
                                     mem.* + compile.traces.* telemetry;
                                     YTK_HEALTH / YTK_HEALTH_STRICT knobs
  recorder                           flight recorder: bounded event ring +
                                     postmortem flight_<ts>.json dump on
                                     abnormal exit; YTK_FLIGHT_* knobs
  HealthError                        strict-mode sentinel escalation
  SLOBurnSentinel                    serving SLO burn-rate alarm
                                     (health.slo_burn)

Serve-side request tracing + metrics history (obs/trace.py,
Registry.history — docs/observability.md "Request tracing"):

  trace                              per-hop request tracing: deterministic
                                     head sampler, X-Ytk-Trace context
                                     propagation, tail-retained exemplar
                                     ring (/admin/traces);
                                     YTK_TRACE_SAMPLE / _SEED / _EXEMPLARS
  start_history_sampler              per-metric (ts, value) rings sampled
                                     by the obs heartbeat thread, exported
                                     at /metrics?history=1;
                                     YTK_OBS_HISTORY_{N,S}

Model-quality plane (obs/quality.py — docs/observability.md
"Model-quality plane"):

  quality                            train-time `<model>.sketch.json` GK
                                     baselines, serve-side drift/
                                     calibration monitor (deterministic
                                     row sampler, PSI/KS, health.drift /
                                     health.calibration sentinels),
                                     fleet merge of per-replica sketches;
                                     YTK_QUALITY_* / YTK_HEALTH_DRIFT_*

Profiling plane (obs/profiler.py — docs/observability.md "Profiling
plane"):

  profiler                           ytkprof: phase accounting with
                                     settled wall time + per-phase
                                     jax.profiler captures (device-time
                                     buckets per span, top-k kernel
                                     table), compile ledger (program
                                     label + abstract-signature diff →
                                     named retrace culprits), background
                                     memory-watermark sampler with
                                     phase-attributed peaks;
                                     YTK_PROF / YTK_PROF_* knobs
"""

from .core import (  # noqa: F401
    NOOP_SPAN,
    REGISTRY,
    Registry,
    Span,
    configure,
    current_span,
    enabled,
    event,
    flush,
    gauge,
    inc,
    record_collective,
    reset,
    root_span,
    set_identity,
    snapshot,
    span,
    spans_between,
    step_span,
)
from .export import (  # noqa: F401
    chrome_trace_events,
    exemplar_trace_events,
    export_chrome_trace,
    export_jsonl,
    load_jsonl,
)
from .heartbeat import (  # noqa: F401
    Heartbeat,
    heartbeat,
    start_history_sampler,
    stop_history_sampler,
)
from . import health, profiler, recorder, scopes, trace  # noqa: F401
from .health import HealthError, SLOBurnSentinel  # noqa: F401
from .trace import TRACE_HEADER, configure_tracing  # noqa: F401
