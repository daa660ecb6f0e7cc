"""Structured telemetry for the K-FAC training stack.

* :mod:`.telemetry` — spans, counters, gauges, histograms in a
  process-wide registry (no-op when disabled).
* :mod:`.export` — Prometheus textfile, JSONL stream, rank-aware summary.
* :mod:`.diagnostics` — the in-graph K-FAC health-key vocabulary.
* :mod:`.trace` — the flight recorder: per-host append-only structured
  event log with cross-process correlation keys (no-op when disabled).
* :mod:`.phases` — the step's phase vocabulary: ``phase(name)`` puts a
  ``jax.named_scope`` on the ops traced inside it (always on, metadata only).
* :mod:`.device_phases` — device time by program run and phase from a
  profiler trace (``python -m kfac_pytorch_tpu.observability.device_phases``);
  imported only where a trace is read.

The recompile detector (``RecompileMonitor``) lives in
:mod:`kfac_pytorch_tpu.compile_cache` next to the compilation-cache setup
it watches.
"""

from kfac_pytorch_tpu.observability.diagnostics import (  # noqa: F401
    LAYER_COND_KEYS,
    SCALAR_KEYS,
    diagnostic_metrics,
)
from kfac_pytorch_tpu.observability.export import (  # noqa: F401
    flush_jsonl,
    prometheus_lines,
    summary_table,
    write_prometheus,
)
from kfac_pytorch_tpu.observability.phases import PHASES, phase  # noqa: F401
from kfac_pytorch_tpu.observability.telemetry import (  # noqa: F401
    Span,
    Telemetry,
    configure,
    get_telemetry,
)
from kfac_pytorch_tpu.observability.trace import (  # noqa: F401
    TraceRecorder,
    configure_trace,
    get_trace,
)
