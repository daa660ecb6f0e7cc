"""From a profiler trace (``.xplane.pb``) to numbers: device busy intervals,
per-operation totals, collective intervals, and the idle gaps by what the
host was doing in them. Read with ``jax.profiler.ProfileData`` alone.

What the planes of a v5e trace are is written down in PERF.md (section 3,
"Reading a trace"): each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` holds one event per executed HLO operation, named by its whole
HLO line (a ``while`` encloses its body's events), whose line ``XLA Modules``
holds one event per executed program and whose line ``Async XLA Ops`` holds the
overlapped copies (not counted as busy); the host is ``/host:CPU``, one line per
thread, and the benchmark's own spans (``put_global_batch``, ``dispatch``,
``metric_fetch``, and ``bench_window`` round the stretch whose busy and idle
time is read) are events on the main thread's line.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("put_global_batch", "dispatch", "metric_fetch")
WINDOW_SPAN = "bench_window"  # the harness's span round the steps whose busy and idle time is read
COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals ``a`` that no interval of merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """``{name: seconds}`` of self time: an event's duration less the part
    its nested children cover, so that an enclosing ``while`` does not count
    its body twice. ``events`` are ``(start, end, name)``."""
    out = {}
    stack = []  # (end, name, start, child_time)

    def close(item):
        end, name, start, child = item
        out[name] = out.get(name, 0.0) + max(0, (end - start) - child)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            top = stack[-1]
            stack[-1] = (top[0], top[1], top[2], top[3] + (min(e, top[0]) - s))
        stack.append((e, name, s, 0))
    while stack:
        close(stack.pop())
    return {k: v * 1e-9 for k, v in out.items()}


def short_name(text):
    """An operation's name as the trace gives it is its whole HLO line;
    keep the result's name, and a custom call's target."""
    name = text.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name} {m.group(1)}" if m else name


def read_planes(path):
    """``{"devices": {n: {"ops": [(s, e, name)], "modules": [...]}},
    "host": [(s, e, name)], "window": [(s, e)]}`` in nanoseconds."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host, window = {}, [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [(e.start_ns, e.start_ns + e.duration_ns, short_name(e.name))
                                for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                    elif e.name == WINDOW_SPAN:
                        window.append((e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": devices, "host": host, "window": window}


def reduce(planes, chips, top=10):
    """The numbers the harness and the per-layer readers take. The window is
    the ``bench_window`` span where the trace has one, else the extent of the
    host's own spans (first start to last end), else of the device
    operations. ``module_runs`` are the first device's program runs in the
    whole trace, in order, each with its seconds on the device."""
    devices = {n: d for n, d in sorted(planes["devices"].items())[:chips]}
    if not devices or not any(d["ops"] for d in devices.values()):
        raise RuntimeError("trace_reduce: no device operation in the trace")
    marks = planes.get("window") or planes["host"] or [ev for d in devices.values() for ev in d["ops"]]
    lo, hi = min(m[0] for m in marks), max(m[1] for m in marks)
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in planes["host"] if e > lo and s < hi]
    busy, exposed, collective, op_totals = [], [], [], {}
    for d in devices.values():
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in d["ops"] if e > lo and s < hi]
        busy_iv = union((s, e) for s, e, _ in ops)
        busy.append(total(busy_iv))
        coll_iv = union((s, e) for s, e, n in ops if COLLECTIVE.match(n))
        other_iv = union((s, e) for s, e, n in ops if not COLLECTIVE.match(n))
        collective.append(total(coll_iv))
        exposed.append(total(subtract(coll_iv, other_iv)))
        for name, sec in self_times(ops).items():
            op_totals[name] = op_totals.get(name, 0.0) + sec / len(devices)
    # idle gaps of the first device, split by the host's own spans that cover them
    # (the spans are one thread's and do not overlap); what none covers is "other"
    first = next(iter(devices.values()))
    busy0 = union((max(s, lo), min(e, hi)) for s, e, _ in first["ops"] if e > lo and s < hi)
    gaps_by = {}
    spans_sorted = sorted(spans)
    for gs, ge in subtract([(lo, hi)], busy0):
        covered = 0
        for s, e, name in spans_sorted:
            if e <= gs:
                continue
            if s >= ge:
                break
            part = min(e, ge) - max(s, gs)
            gaps_by[name] = gaps_by.get(name, 0.0) + part * 1e-9
            covered += part
        if ge - gs > covered:
            gaps_by["other"] = gaps_by.get("other", 0.0) + (ge - gs - covered) * 1e-9
    n = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) * 1e-9 / n,
        "collective_s": sum(collective) * 1e-9 / n,
        "collective_exposed_s": sum(exposed) * 1e-9 / n,
        "op_seconds": op_totals,
        "top_ops": [[k, v] for k, v in sorted(op_totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(gaps_by.items(), key=lambda kv: -kv[1])[:top]],
        "modules": sorted({n for d in devices.values() for _, _, n in d["modules"]}),
        "module_runs": [[n, (e - s) * 1e-9] for s, e, n in sorted(first["modules"])],
    }


def seconds_by_kind(module_runs, dispatched, program="jit_train_step"):
    """``{kind: [seconds on the device, ...]}``: the runs of the step program
    (the three K-FAC programs and the SGD twin all carry its name, and differ
    by the fingerprint in brackets) matched in order to the kinds the harness
    dispatched under the trace. Nothing (``{}``) where the counts differ, or
    where one fingerprint served two kinds or one kind had two: the trace is
    then not what the harness drove."""
    runs = [(n, sec) for n, sec in module_runs if n.startswith(program)]
    if len(runs) != len(dispatched):
        return {}
    out, kind_of, name_of = {}, {}, {}
    for (name, sec), kind in zip(runs, dispatched):
        if kind_of.setdefault(name, kind) != kind or name_of.setdefault(kind, name) != name:
            return {}
        out.setdefault(kind, []).append(sec)
    return out


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"trace_reduce: no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir, chips):
    return reduce(read_planes(find_xplane(trace_dir)), chips)
