"""Device time of each program run, split by phase, from a profiler trace.

    python -m kfac_pytorch_tpu.observability.device_phases <trace_dir | file.xplane.pb>

prints one table per program: runs, median milliseconds per phase, share of
the run, GFLOP/s and GB/s per phase.

The step programs enter ``jax.named_scope(<phase>)`` where each phase's work
is traced (:mod:`.phases`), so every op's name path carries the phase. The
profiler writes that path as the stat ``tf_op`` of the op's *event metadata*
on the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, beside ``flops``,
``bytes_accessed`` and ``program_id``; a program run is one event of the
``XLA Modules`` line. ``jax.profiler.ProfileData`` shows event stats but not
event-metadata stats, and the generated ``xplane_pb2`` lives in tensorflow,
so this module decodes the protobuf wire format of the seven messages of
``tsl/profiler/protobuf/xplane.proto`` itself: no dependency, and nothing of
it is imported unless a trace is read.

Reckoning:

* an op's time is its *self* time: its duration less what its nested
  children cover, so a ``while`` does not count its body twice;
* an op belongs to the run whose interval holds its start;
* an op's phase is the innermost :data:`~.phases.PHASES` component of its
  ``tf_op`` path (``.../model/jvp(TransformerLM)/block_0/qkv/kfac_capture/
  dot_general`` is capture, not model), ``unscoped`` where there is none;
* a fusion carries the name of its root instruction, so a fusion XLA built
  across a phase boundary is charged whole to one phase;
* ``flops`` and ``bytes_accessed`` are summed over ops that enclose no other
  op (a ``while`` repeats its body's counts);
* ``idle`` is the part of a run's interval in which no op ran.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
import struct
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from kfac_pytorch_tpu.observability.phases import PHASES

UNSCOPED = "unscoped"
IDLE = "idle"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_COMPONENT = re.compile(r"[A-Za-z_]\w*$")


# -- the wire format ------------------------------------------------------
#
# A message is a sequence of (tag, value): tag = field_number << 3 | type,
# type 0 a varint, 1 eight bytes, 2 a length and that many bytes, 5 four
# bytes. Field numbers are those of xplane.proto.


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    """The varint at ``pos`` and the position after it."""
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int) -> Iterator[Tuple[int, int, int, int]]:
    """``(field, wire_type, value, value_end)`` of one message's fields.
    For type 2, ``value`` is where the payload starts and ``value_end``
    where it ends; otherwise ``value`` is the number (unsigned). Most
    varints of a trace are one byte: those are read in line."""
    while pos < end:
        tag = buf[pos]
        pos += 1
        if tag & 0x80:
            tag, pos = _varint(buf, pos - 1)
        wire = tag & 7
        if wire == 0 or wire == 2:
            value = buf[pos]
            pos += 1
            if value & 0x80:
                value, pos = _varint(buf, pos - 1)
            if wire == 0:
                yield tag >> 3, 0, value, pos
            else:
                yield tag >> 3, 2, pos, pos + value
                pos += value
        elif wire == 1:
            yield tag >> 3, 1, int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
            pos += 8
        elif wire == 5:
            yield tag >> 3, 5, int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
            pos += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {pos}")
    if pos != end:
        raise ValueError("xplane: a message ends past its length")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


def _text(buf: bytes, start: int, end: int) -> str:
    return buf[start:end].decode("utf-8", "replace")


def _stat(buf, pos, end):
    """``XStat`` -> ``(metadata_id, kind, value)``; ``kind`` is the name of
    the ``value`` oneof's member."""
    meta, kind, value = 0, None, None
    for field, _, v, e in _fields(buf, pos, end):
        if field == 1:
            meta = v
        elif field == 2:
            kind, value = "double_value", struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif field == 3:
            kind, value = "uint64_value", v
        elif field == 4:
            kind, value = "int64_value", _signed(v)
        elif field == 5:
            kind, value = "str_value", _text(buf, v, e)
        elif field == 6:
            kind, value = "bytes_value", bytes(buf[v:e])
        elif field == 7:
            kind, value = "ref_value", v
    return meta, kind, value


def _event(buf, pos, end):
    """``XEvent`` -> ``(metadata_id, offset_ps, duration_ps)``; its stats
    are not read (the device's own clock repeats offset and duration)."""
    meta = offset = duration = 0
    for field, _, v, _e in _fields(buf, pos, end):
        if field == 1:
            meta = v
        elif field == 2:
            offset = _signed(v)
        elif field == 3:
            duration = _signed(v)
    return meta, offset, duration


def _line(buf, pos, end, want):
    """``XLine`` -> ``{"name", "timestamp_ns", "events"}``; events are
    decoded only where ``want(name)`` (a line's name precedes its events in
    field order, but is looked up first in case a writer orders otherwise)."""
    name, timestamp_ns, spans = "", 0, []
    for field, _, v, e in _fields(buf, pos, end):
        if field == 2:
            name = _text(buf, v, e)
        elif field == 3:
            timestamp_ns = _signed(v)
        elif field == 4:
            spans.append((v, e))
    events = [_event(buf, s, e) for s, e in spans] if want(name) else None
    return {"name": name, "timestamp_ns": timestamp_ns, "n_events": len(spans), "events": events}


def _map_entry(buf, pos, end):
    key, span = 0, (pos, pos)
    for field, _, v, e in _fields(buf, pos, end):
        if field == 1:
            key = v
        elif field == 2:
            span = (v, e)
    return key, span


def _event_metadata(buf, pos, end):
    """``XEventMetadata`` -> ``(name, [stat, ...])``."""
    name, stats = "", []
    for field, _, v, e in _fields(buf, pos, end):
        if field == 2:
            name = _text(buf, v, e)
        elif field == 5:
            stats.append(_stat(buf, v, e))
    return name, stats


def _stat_metadata_name(buf, pos, end):
    for field, _, v, e in _fields(buf, pos, end):
        if field == 2:
            return _text(buf, v, e)
    return ""


def _plane(buf, pos, end, want_plane, want_line):
    """``XPlane`` -> ``{"name", "lines", "event_metadata"}``, or only its
    name where ``want_plane(name)`` is false. A stat of an event
    metadata is resolved to ``{stat name: value}``, a ``ref_value`` to the
    string it refers to."""
    name, lines, events, stats = "", [], [], []
    for field, _, v, e in _fields(buf, pos, end):
        if field == 2:
            name = _text(buf, v, e)
        elif field == 3:
            lines.append((v, e))
        elif field == 4:
            events.append((v, e))
        elif field == 5:
            stats.append((v, e))
    if not want_plane(name):
        return {"name": name}
    stat_names = {}
    for s, e in stats:
        key, (vs, ve) = _map_entry(buf, s, e)
        stat_names[key] = _stat_metadata_name(buf, vs, ve)
    event_metadata = {}
    for s, e in events:
        key, (vs, ve) = _map_entry(buf, s, e)
        md_name, md_stats = _event_metadata(buf, vs, ve)
        event_metadata[key] = {
            "name": md_name,
            "stats": {
                stat_names.get(m, str(m)): (stat_names.get(v, "") if kind == "ref_value" else v)
                for m, kind, v in md_stats
            },
        }
    return {
        "name": name,
        "lines": [_line(buf, s, e, want_line) for s, e in lines],
        "event_metadata": event_metadata,
    }


def read_xspace(path: str, want_plane=lambda name: True, want_line=lambda name: True) -> List[dict]:
    """The planes of an ``.xplane.pb`` file (``XSpace.planes``), decoded as
    far as the two predicates ask. ``ValueError`` for a file that is not
    one, or is cut short."""
    with open(path, "rb") as f:
        buf = f.read()
    try:
        return [
            _plane(buf, v, e, want_plane, want_line)
            for field, wire, v, e in _fields(buf, 0, len(buf))
            if field == 1 and wire == 2
        ]
    except IndexError:
        raise ValueError(f"xplane: {path} ends inside a message") from None


# -- from planes to phases ------------------------------------------------


def phase_of(tf_op: Optional[str]) -> str:
    """The innermost phase component of an op's name path. A component that
    a transformation wrapped (``transpose(jvp(kfac_capture))``) counts by
    the name inside."""
    for comp in reversed((tf_op or "").split("/")):
        m = _COMPONENT.search(comp.rstrip(":)"))
        if m and m.group(0) in PHASES:
            return m.group(0)
    return UNSCOPED


def self_and_leaf(events: List[Tuple[int, int, int]]) -> List[Tuple[int, int, bool]]:
    """For ``(start, end, key)`` events of one line, ``(key, self time,
    encloses no other event)`` each: the self time is the duration less what
    nested children cover (benchmarks/trace_reduce.py::self_times reckons
    the same way)."""
    out = []
    stack = []  # [end, key, start, child time, is leaf]

    def close(item):
        end, key, start, child, leaf = item
        out.append((key, max(0, (end - start) - child), leaf))

    for start, end, key in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            top = stack[-1]
            top[3] += min(end, top[0]) - start
            top[4] = False
        stack.append([end, key, start, 0, True])
    while stack:
        close(stack.pop())
    return out


def find_xplane(path: str) -> str:
    """``path`` itself where it is a file, else the newest ``.xplane.pb``
    under the profiler's ``plugins/profile/<time>/`` of that directory."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"device_phases: no .xplane.pb under {path}")
    return found[-1]


def program_runs(path: str) -> List[dict]:
    """Every run of every program on every TPU plane of the trace, in order
    of start within a device: ``{"device", "program", "start_ps",
    "duration_ps", "phases": {phase: {"ps", "flops", "bytes", "ops"}}}``
    with the phases :data:`PHASES`, ``unscoped`` and ``idle`` (picoseconds
    of self time; ``idle`` has only ``ps``), and ``"unscoped_ops"``:
    ``{op name: ps}`` of the ops that carry no phase."""
    planes = read_xspace(
        find_xplane(path),
        want_plane=lambda name: bool(DEVICE_PLANE.match(name)),
        want_line=lambda name: name in (OPS_LINE, MODULES_LINE),
    )
    runs = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        metadata = plane["event_metadata"]
        lines = {line["name"]: line for line in plane["lines"] if line["events"] is not None}
        if MODULES_LINE not in lines or OPS_LINE not in lines:
            continue

        def absolute(line):
            base = line["timestamp_ns"] * 1000
            return [(base + off, base + off + dur, meta) for meta, off, dur in line["events"]]

        modules = sorted(absolute(lines[MODULES_LINE]))
        starts = [s for s, _, _ in modules]
        mine = []
        for s, e, meta in modules:
            phases = {p: {"ps": 0, "flops": 0, "bytes": 0, "ops": 0} for p in PHASES + (UNSCOPED,)}
            mine.append({
                "device": int(m.group(1)),
                "program": metadata.get(meta, {}).get("name", str(meta)),
                "start_ps": s, "duration_ps": e - s, "phases": phases, "unscoped_ops": {},
            })
        ops = absolute(lines[OPS_LINE])
        phase_by_meta = {}
        for index, self_ps, leaf in self_and_leaf([(s, e, k) for k, (s, e, _) in enumerate(ops)]):
            s, _, meta_id = ops[index]
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= modules[i][1]:
                continue  # an op outside every program run
            md = metadata.get(meta_id, {"name": "", "stats": {}})
            phase = phase_by_meta.get(meta_id)
            if phase is None:
                phase = phase_by_meta[meta_id] = phase_of(md["stats"].get("tf_op"))
            cell = mine[i]["phases"][phase]
            cell["ps"] += self_ps
            cell["ops"] += 1
            if leaf:
                cell["flops"] += max(0, md["stats"].get("flops") or 0)
                cell["bytes"] += max(0, md["stats"].get("bytes_accessed") or 0)
            if phase == UNSCOPED:
                name = md["name"].split(" = ", 1)[0]
                mine[i]["unscoped_ops"][name] = mine[i]["unscoped_ops"].get(name, 0) + self_ps
        for run in mine:
            busy = sum(c["ps"] for c in run["phases"].values())
            run["phases"][IDLE] = {"ps": max(0, run["duration_ps"] - busy)}
        runs += mine
    return runs


def table(program: str, runs: List[dict]) -> str:
    """One program's table: per phase the median milliseconds over its runs,
    the share of the median run, and the rates over all runs."""
    total_ms = statistics.median(r["duration_ps"] for r in runs) * 1e-9
    rows = [f"{program}: {len(runs)} run(s), median {total_ms:.3f} ms on the device",
            f"  {'phase':<14}{'median ms':>12}{'share %':>9}{'GFLOP/s':>11}{'GB/s':>9}{'ops/run':>9}"]
    for phase in PHASES + (UNSCOPED, IDLE):
        cells = [r["phases"][phase] for r in runs]
        ps = sum(c["ps"] for c in cells)
        if not ps:
            continue
        ms = statistics.median(c["ps"] for c in cells) * 1e-9
        line = f"  {phase:<14}{ms:>12.3f}{100 * ms / total_ms if total_ms else 0:>9.2f}"
        if phase != IDLE:
            # flops / ps = 1e12 flop/s = 1e3 GFLOP/s
            line += (f"{sum(c['flops'] for c in cells) / ps * 1e3:>11.1f}"
                     f"{sum(c['bytes'] for c in cells) / ps * 1e3:>9.1f}"
                     f"{sum(c['ops'] for c in cells) / len(runs):>9.0f}")
        rows.append(line)
    unscoped: Dict[str, int] = {}
    for r in runs:
        for name, ps in r["unscoped_ops"].items():
            unscoped[name] = unscoped.get(name, 0) + ps
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:3]
    if top:
        rows.append("  largest unscoped ops: " + ", ".join(
            f"{name} {ps * 1e-9 / len(runs):.3f} ms" for name, ps in top))
    return "\n".join(rows)


def report(path: str) -> str:
    """The tables of every program of the trace, in order of first run."""
    groups: Dict[str, List[dict]] = {}
    for run in program_runs(path):
        groups.setdefault(run["program"], []).append(run)
    if not groups:
        return f"device_phases: no program run on a /device:TPU plane of {find_xplane(path)}"
    return "\n\n".join(table(program, runs) for program, runs in groups.items())


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    print(report(argv[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
