"""lightgbm_tpu.obs — unified runtime telemetry.

One low-overhead observability layer shared by training, checkpointing and
serving:

- ``registry``: a process-wide, thread-safe counter/gauge/summary/histogram
  registry with Prometheus text exposition and JSON snapshots.
  serving/metrics.py and profiling.py's compile-cache counters are both
  backed by it.
- ``costmodel``: XLA cost-model extraction (FLOPs / bytes / memory per
  compiled entry point via AOT ``cost_analysis``) and per-phase roofline
  attribution against the detected chip's peaks — feeds ``GET /roofline``
  and the perf gate.
- ``perfgate``: deterministic semantic perf counters + baseline comparison
  (``PERF_COUNTERS.json``, ``tools/perf_gate.py``).
- ``trace``: the one span recorder (always on, a bounded in-memory ring,
  every span also an annotation on the profiler's clock), a JSON-lines event
  stream, an on-demand ``jax.profiler`` Perfetto capture helper for a
  configurable iteration window, and ``capture_phases``, which reduces a
  capture to device seconds by ``lgbm.*`` scope.
- ``health``: host dispatch for device-side health flags (non-finite
  grad/hess, zero-positive-gain waves) that the training step piggy-backs
  on existing reductions — warn, checkpoint-and-abort, or raise.
- ``reqtrace``: request-scoped span trees with tail-based sampling —
  one trace per admitted serving request (propagated across fleet hops
  via the ``x-lgbm-trace`` header) or per streamed training iteration,
  emitted as ``span`` events on the shared EventStream.
- ``slo``: declarative SLOs (latency/availability/throughput) judged as
  Google-SRE multi-window burn rates over registry metrics; ``/slo`` on
  both StatsServers, ``lgbm_slo_*`` gauges, warn-only HealthMonitor
  routing.
- ``server``: an optional lightweight stats HTTP endpoint during training
  (Prometheus text + JSON snapshot + healthz + federated cluster routes).
- ``distributed``: multi-process telemetry — metric federation (global
  ``process=``/``host=`` labels, once-per-block snapshot allgather served
  from ``/metrics/cluster`` + ``/stats/cluster``), per-block comm/compute
  attribution with straggler-skew detection, and a crash-dumping flight
  recorder (``<obs_event_file>.<process>.crash.jsonl``).
- ``runtime``: ``TrainingObs``, the per-booster facade built from the
  ``observability=none|basic|full`` config knob that the boosting loop
  drives.

Nothing is exported by default (``observability=none``): spans are still
recorded in memory, every other hook is a no-op, and the training loop's
compiled program is byte-identical to an uninstrumented build.
"""
from .health import (HEALTH_NONFINITE, HEALTH_NONFINITE_GAIN,  # noqa: F401
                     HEALTH_STUMP, HEALTH_VEC_LEN, HEALTH_WAVES,
                     HealthMonitor, HealthReport, health_vec)
from .costmodel import (CHIP_PEAKS, CostModel, detect_peaks,  # noqa: F401
                        get_cost_model, roofline_snapshot)
from .registry import (Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry, Summary, get_registry)
from .reqtrace import (NULL_REQ_SPAN, NULL_TRACER,  # noqa: F401
                       NullRequestTracer, ReqSpan, RequestTracer,
                       TRACE_HEADER, format_trace_header, keep_decision,
                       new_trace_id, parse_trace_header)
from .runtime import TrainingObs, resolve_health_action  # noqa: F401
from .server import StatsServer  # noqa: F401
from .slo import SloEngine, SloSpec  # noqa: F401
from .trace import (EventStream, Tracer, capture_phases,  # noqa: F401
                    perfetto_trace, recorded_spans)
from .distributed import (DistributedObs, FlightRecorder,  # noqa: F401
                          merge_prometheus_texts, process_env,
                          straggler_skew)
