"""Unified run telemetry: trace spans + metrics registry + JSONL run report.

Four pieces, one artifact:

- :mod:`photon_tpu.obs.trace` — hierarchical host-wall spans
  (``span("cd/iter3/per-user/solve")``), thread-safe, nestable across the
  ingest pipeline's stage threads; under a ``jax.profiler`` session each
  also shows as ``photon/<path>`` on the device trace's clock.
- :mod:`photon_tpu.obs.metrics` — process-global counters / gauges /
  histograms with labels; the solve cache, pipeline stages, replay cache,
  shape bucketing, and optimizers all publish here.
- :mod:`photon_tpu.obs.host` — host pauses on the span clock: every
  collection (``host/gc/gen<N>``) and every stall of the process
  (``host/stall/<cause>``), from the one host sampler thread.
- :mod:`photon_tpu.obs.report` — the run-report finalizer: spans + metrics
  + coordinate-descent tracker + environment as schema-stable JSONL
  (``--telemetry-out`` on every CLI driver) and as
  ``PhotonOptimizationLogEvent`` payloads.

Drivers call :func:`begin_run` at entry (fresh spans/metrics/phase timers —
stale state from a previous in-process invocation never leaks into this
run's report) and ``finalize_run_report`` at exit.
"""

from photon_tpu.obs.export import (  # noqa: F401
    MockCollector,
    OTLPExporter,
    active_exporter,
    exporter_health,
    install_exporter,
    maybe_install_exporter,
    uninstall_exporter,
)
from photon_tpu.obs.host import start_sentinel  # noqa: F401
from photon_tpu.obs.metrics import (  # noqa: F401
    PROMETHEUS_CONTENT_TYPE,
    MetricsRegistry,
    registry,
    render_prometheus,
    reset_registry,
)
from photon_tpu.obs.report import (  # noqa: F401
    TELEMETRY_SCHEMA,
    collect_run_records,
    finalize_run_report,
    telemetry_sink_health,
    validate_record,
    write_run_report,
)
from photon_tpu.obs.slo import SLOTracker  # noqa: F401
from photon_tpu.obs.trace import (  # noqa: F401
    FlightRecorder,
    SpanRecord,
    TraceContext,
    attach_context,
    current_span_path,
    extract_context,
    flight_recorder,
    get_spans,
    merge_trace_dumps,
    mint_context,
    record_span,
    reset_flight_recorder,
    reset_tracer,
    span,
    tracer,
)


def begin_run() -> None:
    """Reset all run-scoped telemetry state: spans, registry metrics, the
    ``Timed`` phase records, and the shared solve-cache counters (compiled
    executables are kept — only the counters are run-scoped), so a second
    driver invocation in one process starts from a clean slate. Starts the
    host-pause sentinel (:mod:`photon_tpu.obs.host`) for the driver."""
    from photon_tpu.algorithm.solve_cache import default_cache
    from photon_tpu.utils.timed import Timed

    reset_tracer()
    reset_flight_recorder()
    reset_registry()
    Timed.reset()
    default_cache().reset_stats()
    start_sentinel()
