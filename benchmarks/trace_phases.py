"""From a profiler trace to device time by program run and phase.

The step programs enter a named scope where each phase's work is traced
(``kfac_pytorch_tpu/observability/phases.py::PHASES``), and the program's own
reader, ``observability/device_phases.py::program_runs``, gives for every run
of every program the self time of its ops by the innermost phase of their
names, ``unscoped`` for ops with none and ``idle`` for the gaps inside the
run. This file takes the runs of the step program on the first device,
matches them to the kinds the harness dispatched under the trace exactly as
``trace_reduce.seconds_by_kind`` does, and hands the per-layer readers

    {kind: [{phase: milliseconds} per run, in order]}

for whatever phase names the program's ``PHASES`` holds, so a phase a later
PR adds is read by a new file under ``metrics/`` alone. It gives nothing
(``{}``, no exception) where the program has no such reader (before PR 25),
where the runs do not match the kinds dispatched, or where a run's phases
contradict the kind the order gave it: a ``refresh`` run with no
``kfac_refresh`` op, a twin's run with a ``kfac_*`` op, a K-FAC run of a
program compiled without the scopes (PERF.md, section 3).
"""

from __future__ import annotations

import importlib
import statistics

PROGRAM = "jit_train_step"
# phases a run of a kind has to show time in, and may not
HOLDS = {"factors": ("kfac_capture",), "refresh": ("kfac_capture", "kfac_refresh"), "plain": ("kfac_apply",)}
LACKS = {"factors": ("kfac_refresh",), "plain": ("kfac_capture", "kfac_refresh")}
KINDS = ("factors", "refresh", "plain", "twin")  # the kinds of run, in the order of choice where the window's counts tie


def consistent(kind, phases):
    if kind == "twin":
        return not any(ms > 0 for name, ms in phases.items() if name.startswith("kfac_"))
    return (all(phases.get(p, 0) > 0 for p in HOLDS.get(kind, ()))
            and not any(phases.get(p, 0) > 0 for p in LACKS.get(kind, ())))


def by_kind(trace_dir, dispatched, program=PROGRAM):
    try:
        device_phases = importlib.import_module("kfac_pytorch_tpu.observability.device_phases")
    except ImportError:
        return {}
    runs = [r for r in device_phases.program_runs(trace_dir) if r["program"].startswith(program)]
    runs = [r for r in runs if r["device"] == min(q["device"] for q in runs)]
    if len(runs) != len(dispatched):
        return {}
    out, kind_of, name_of = {}, {}, {}
    for run, kind in zip(runs, dispatched):
        name = run["program"]
        phases = {p: cell["ps"] * 1e-9 for p, cell in run["phases"].items()}
        if (kind_of.setdefault(name, kind) != kind or name_of.setdefault(kind, name) != name
                or not consistent(kind, phases)):
            return {}
        out.setdefault(kind, []).append(phases)
    return out


def median_ms(run, names, kind=None, share=False):
    """The median, over the traced runs of one kind, of the self time under
    the phases ``names`` together (with ``share``: of its percentage of the
    run's whole device time). The kind: the one given, else the kind the
    window ran most among those whose runs hold the phases; the SGD twin is
    never chosen. ``None`` where no such run is in the trace."""
    by = run.get("phase_ms") or {}

    def value(phases):
        ms = sum(phases.get(n, 0.0) for n in names)
        return 100.0 * ms / sum(phases.values()) if share else ms

    total = lambda k: statistics.median(value(p) for p in by[k])
    if kind is None:
        ran = [r["kind"] for r in run["records"]]
        held = [k for k in sorted(by, key=KINDS.index) if k != "twin" and total(k) > 0]  # max takes a tie's first
        if not held:
            return None
        kind = max(held, key=ran.count)
    return total(kind) if kind in by else None


def window_seconds(phase_ms, traced_kinds):
    """``[[phase, seconds], ...]``, largest first: the phases' totals over
    the runs of the ``bench_window`` stretch, which are the first runs of
    each kind, as many as ``traced_kinds`` lists of it."""
    totals = {}
    for kind in set(traced_kinds):
        for phases in phase_ms.get(kind, [])[:traced_kinds.count(kind)]:
            for name, ms in phases.items():
                totals[name] = totals.get(name, 0.0) + ms * 1e-3
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1]) if v > 0][:10]
