"""Process-wide telemetry registry: spans, counters, gauges, histograms.

The measurement substrate the ROADMAP's perf PRs report against. The
reference ships nothing beyond wall-clock totals and tqdm postfixes
(SURVEY.md §5); distributed K-FAC work needs to know *where* a step's time
goes (factor accumulation vs eigh vs precondition vs comm) and whether the
curvature approximation is healthy before any scheduling/perf decision can
be judged — the per-phase cost models of arXiv:2107.06533 and the
per-layer factor breakdowns of arXiv:2206.15143 both start from exactly
this data.

Design constraints, in priority order:

* **Near-zero overhead when disabled.** Telemetry is off by default;
  ``span()`` on a disabled registry returns a shared no-op singleton — no
  allocation, no clock read — so the hot loop pays one attribute lookup
  and a branch (<1% of even a 1 ms step). Counters/gauges short-circuit
  the same way.
* **Host-side only.** Nothing here emits XLA ops: spans inside jitted code
  measure *tracing* time (name them ``trace/...``), device-inclusive wall
  time comes from host-side spans that ``block()`` on a step output, and
  in-graph health numbers flow out of the step as the diagnostics pytree
  (preconditioner.py) — so the compiled program is bit-identical with
  telemetry on or off. Device time by phase is read from a profiler trace
  by the ops' own scope names (phases.py, device_phases.py).
* **Fixed metric names.** Every span/counter/gauge name is a string
  literal registered in docs/OBSERVABILITY.md (enforced by
  scripts/check_metric_names.py); no f-string names, so exporter output
  is greppable and the registry lint stays sound.

Spans nest freely (each records its own duration into its own histogram;
there is no implicit parent/child renaming) and are reentrant. The
registry is GIL-thread-safe for the dict/list operations it performs; it
is not designed for cross-process sharing — each process owns one, and
rank-aware aggregation happens at summary time (export.py).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

# Per-histogram sample cap: one float per observation, so an unbounded
# 3-day run cannot grow host memory without bound. At the cap the
# reservoir keeps the FIRST samples (steady-state spans are stationary;
# p50/p95 from the first 64k observations is the same estimate).
_HIST_CAP = 65536


class _NullSpan:
    """Shared no-op span for the disabled path: zero allocation per use."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def block(self, obj) -> None:  # matches Span.block
        pass


_NULL_SPAN = _NullSpan()


class Span:
    """Context-manager timer recording seconds into a named histogram.

    ``block(obj)`` registers a value (typically the step's output pytree)
    to ``jax.block_until_ready`` on exit, so the recorded duration includes
    the device work an async dispatch would otherwise hide. Without it a
    span around a jitted call times only dispatch.

    The span also enters a ``jax.profiler.TraceAnnotation`` of its name, so
    that under a profiler trace (``--profile-epoch``) the trainers' host
    spans lie on ``/host:CPU`` of the same ``.xplane.pb`` as the device ops,
    on one clock; with no trace running the annotation records nothing.
    """

    __slots__ = ("_telemetry", "_name", "_t0", "_sync", "_annotation")

    def __init__(self, telemetry: "Telemetry", name: str):
        self._telemetry = telemetry
        self._name = name
        self._t0 = 0.0
        self._sync = None
        self._annotation = None

    def block(self, obj) -> None:
        self._sync = obj

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._sync is not None and self._telemetry.block_spans:
            import jax

            jax.block_until_ready(self._sync)
        self._telemetry.observe(self._name, time.perf_counter() - self._t0)
        self._annotation.__exit__(*exc)
        return False


class Telemetry:
    """One process's metric registry.

    * ``inc(name, by)`` — monotonic counters (events: retraces, steps).
    * ``set_gauge(name, v)`` — last-value-wins scalars (config, derived
      phase costs).
    * ``observe(name, v)`` — histogram samples (span durations, in
      seconds).
    * ``span(name)`` — context-manager timer feeding ``observe``.
    """

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        # Whether Span.block() registrations actually barrier on exit.
        # True gives device-inclusive durations; False records dispatch
        # time only. The overlap plane (KFAC(comm_overlap=True)) needs
        # False: a block_until_ready inside the fused comm/compute region
        # drains the device queue mid-step and re-serializes exactly the
        # collectives the overlap interleaved.
        self.block_spans = True
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.hists: Dict[str, List[float]] = {}

    # -- write side ------------------------------------------------------

    def inc(self, name: str, by: float = 1.0) -> None:
        if not self.enabled:
            return
        self.counters[name] = self.counters.get(name, 0.0) + by

    def set_gauge(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = []
        if len(h) < _HIST_CAP:
            h.append(float(value))

    def span(self, name: str):
        if not self.enabled:
            return _NULL_SPAN
        return Span(self, name)

    def reset(self) -> None:
        self.counters.clear()
        self.gauges.clear()
        self.hists.clear()

    # -- read side -------------------------------------------------------

    def percentiles(
        self, name: str, qs: Tuple[float, ...] = (0.5, 0.95)
    ) -> Optional[Tuple[float, ...]]:
        """Sorted-sample percentiles of one histogram; None if empty."""
        h = self.hists.get(name)
        if not h:
            return None
        s = sorted(h)
        n = len(s)
        return tuple(s[min(n - 1, int(q * n))] for q in qs)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Flat point-in-time view: counters/gauges verbatim, histograms
        reduced to count/sum/p50/p95 — the exporters' input format."""
        out: Dict[str, Dict[str, float]] = {
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "spans": {},
        }
        for name, h in self.hists.items():
            if not h:
                continue
            p50, p95 = self.percentiles(name) or (0.0, 0.0)
            out["spans"][name] = {
                "count": float(len(h)),
                "sum": float(sum(h)),
                "p50": p50,
                "p95": p95,
            }
        return out


_GLOBAL = Telemetry(enabled=False)


def get_telemetry() -> Telemetry:
    """The process-wide registry (disabled until :func:`configure`)."""
    return _GLOBAL


def configure(
    enabled: bool = True, block_spans: Optional[bool] = None
) -> Telemetry:
    """Enable/disable the process-wide registry and return it.

    ``block_spans=False`` turns span ``block()`` barriers into no-ops so
    enabled telemetry cannot serialize an overlapped step (the trainers
    set this automatically when ``KFAC(comm_overlap=True)``); ``None``
    leaves the current setting untouched.
    """
    _GLOBAL.enabled = enabled
    if block_spans is not None:
        _GLOBAL.block_spans = bool(block_spans)
    return _GLOBAL
