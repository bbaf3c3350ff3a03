"""Unified observability layer (DESIGN.md §10).

One subsystem, three concerns, shared across quantize + serve:

* `obs/trace.py`   — `Tracer`: nestable host spans + request lifecycle
  events, emitted as Chrome-trace/Perfetto JSON; every span is also a
  `jax.profiler` TraceAnnotation, so host spans line up with device
  timelines.
* `obs/metrics.py` — `MetricsRegistry`: counters / gauges / histograms
  with a JSONL event-stream sink and Prometheus text exposition.
* `obs/timeline.py` — per-request serve timelines (submit → admit →
  first_token → decode tokens → preempt/resume → retire) reconstructed
  from the tracer's request events, rid-dedup'd across crash-replay
  restarts.
* `obs/validate.py` — pure-python Chrome-trace schema checker (the CI
  smoke gate on every emitted trace).
* `obs/report.py`  — `python -m repro.obs.report DIR` renders a run
  summary table from the sinks.

The cardinal rule (DESIGN.md §10.3): instrumentation is **zero-cost when
disabled and host-sync-free in hot zones**. Disabled tracers/registries
are shared null singletons whose hooks return immediately; enabled ones
only append host dicts — quantities that live on device stay there until
the run's one end-of-run pull.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, NULL_METRICS)
from repro.obs.timeline import (RequestTimeline, dedup_events,  # noqa: F401
                                reconstruct_timelines, request_events,
                                validate_timeline)
from repro.obs.trace import (NULL_TRACER, Span, Tracer,  # noqa: F401
                             next_trace_path)
from repro.obs.validate import (validate_trace,  # noqa: F401
                                validate_trace_file)
