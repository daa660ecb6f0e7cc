"""``observability/device_phases.py``: the wire decoder against the generated
``xplane_pb2`` (where tensorflow imports) and against committed totals
(always) on the traces recorded on a v5e, the reduction by run and phase on
a hand-built trace (innermost phase wins, a ``while`` counts its self time,
an op outside every run is left out) and on the recording of the scoped tiny
LM cell, and the two places the program itself uses it: the command line and
``training/profiling.py::maybe_trace``."""

import json
import os
import struct
import subprocess
import sys

import pytest

from kfac_pytorch_tpu.observability import device_phases as dp
from kfac_pytorch_tpu.observability.phases import PHASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(REPO, "benchmarks", "tests", "recorded_v5e.xplane.pb")
RECORDED_PHASES = os.path.join(REPO, "benchmarks", "tests", "recorded_v5e_phases.xplane.pb")
MS = 10**9  # picoseconds


# -- a tiny encoder, to build a trace by hand -------------------------------


def _varint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(number, value):
    """One field: an int as a varint, bytes/str length-delimited, a float as a double."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


STAT_IDS = {"tf_op": 1, "flops": 2, "bytes_accessed": 3, "program_id": 4}


def _metadata(key, name, **stats):
    kinds = {"tf_op": 5, "flops": 4, "bytes_accessed": 4, "program_id": 3}
    body = _f(1, key) + _f(2, name) + b"".join(
        _f(5, _f(1, STAT_IDS[k]) + _f(kinds[k], v)) for k, v in stats.items())
    return _f(4, _f(1, key) + _f(2, body))


def _line(name, events, timestamp_ns=0):
    return _f(3, _f(2, name) + _f(3, timestamp_ns) + b"".join(
        _f(4, _f(1, meta) + _f(2, offset) + _f(3, duration)
           + _f(4, _f(1, 9) + _f(3, offset)))  # an event stat, which the reader skips
        for meta, offset, duration in events))


def _plane(name, *parts):
    stat_names = b"".join(_f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, n))) for n, i in STAT_IDS.items())
    return _f(1, _f(1, 7) + _f(2, name) + b"".join(parts) + stat_names)


@pytest.fixture
def hand_built(tmp_path):
    """Two runs of one program and one of another on ``/device:TPU:0``:

    run 1 (0..100 ms): a capture product inside the model's forward pass
    (10..30), a ``while`` of the model (40..90) whose body holds an apply op
    (50..60) and a model op (60..85), an op with no name (92..96);
    run 2 (200..250 ms): the same program, one refresh op (210..240);
    run 3 (300..320 ms): the twin, one model op; and one op outside every run.
    """
    md = b"".join([
        _metadata(1, "jit_train_step(11)"),
        _metadata(2, "jit_train_step(22)"),
        _metadata(10, "%fusion.1 = f32[8] fusion(...)", flops=2 * 10**9, bytes_accessed=10**8, program_id=11,
                  tf_op="jit(train_step)/model/jvp(LM)/block_0/qkv/qkv._sow_a/kfac_capture/dot_general:"),
        _metadata(11, "%while.1 = (f32[8]) while(...)", flops=999, bytes_accessed=999,
                  tf_op="jit(train_step)/model/while:"),
        _metadata(12, "%fusion.2 = f32[8] fusion(...)", flops=300, bytes_accessed=30,
                  tf_op="jit(train_step)/model/while/body/kfac_apply/mul:"),
        _metadata(13, "%fusion.3 = f32[8] fusion(...)", flops=500, bytes_accessed=50,
                  tf_op="jit(train_step)/model/while/body/transpose(jvp(LM))/dot_general:"),
        _metadata(14, "%copy.4 = f32[8] copy(...)", flops=0, bytes_accessed=64),
        _metadata(15, "%custom-call.5 = f32[8,8] custom-call(...)", flops=-1, bytes_accessed=0,
                  tf_op="jit(train_step)/kfac_refresh/cholesky:"),
    ])
    modules = _line("XLA Modules", [(1, 0, 100 * MS), (1, 200 * MS, 50 * MS), (2, 300 * MS, 20 * MS)])
    ops = _line("XLA Ops", [
        (10, 10 * MS, 20 * MS), (11, 40 * MS, 50 * MS), (12, 50 * MS, 10 * MS), (13, 60 * MS, 25 * MS),
        (14, 92 * MS, 4 * MS), (15, 210 * MS, 30 * MS), (13, 302 * MS, 15 * MS), (14, 400 * MS, 5 * MS),
    ])
    space = (_plane("/host:CPU", _line("main/1", [(1, 0, 5)]))
             + _plane("/device:TPU:0", md, modules, ops, _line("Steps", [(1, 0, 1)])))
    path = tmp_path / "plugins" / "profile" / "2026_10_01" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space)
    return str(tmp_path)


def test_hand_built_runs_and_phases(hand_built):
    runs = dp.program_runs(hand_built)
    assert [(r["program"], r["duration_ps"]) for r in runs] == [
        ("jit_train_step(11)", 100 * MS), ("jit_train_step(11)", 50 * MS), ("jit_train_step(22)", 20 * MS)]
    first = {p: c["ps"] for p, c in runs[0]["phases"].items() if c["ps"]}
    # innermost wins: the product sown in the forward pass is capture, the op in
    # the model's loop under kfac_apply is apply; the while keeps its self time
    assert first == {"kfac_capture": 20 * MS, "kfac_apply": 10 * MS, "model": (50 - 35 + 25) * MS,
                     dp.UNSCOPED: 4 * MS, dp.IDLE: (100 - 74) * MS}
    assert runs[0]["unscoped_ops"] == {"%copy.4": 4 * MS}
    # flops and bytes of ops that enclose no other: the while's own counts repeat its body's
    assert runs[0]["phases"]["model"] == {"ps": 40 * MS, "flops": 500, "bytes": 50, "ops": 2}
    assert runs[0]["phases"]["kfac_capture"]["flops"] == 2 * 10**9
    assert {p: c["ps"] for p, c in runs[1]["phases"].items() if c["ps"]} == {
        "kfac_refresh": 30 * MS, dp.IDLE: 20 * MS}
    assert runs[1]["phases"]["kfac_refresh"]["flops"] == 0  # a count the compiler does not know
    assert runs[2]["phases"]["model"]["ps"] == 15 * MS
    # every run's phases and idle add up to its time on the device
    for r in runs:
        assert sum(c["ps"] for c in r["phases"].values()) == r["duration_ps"]
        assert set(r["phases"]) == set(PHASES) | {dp.UNSCOPED, dp.IDLE}


def test_hand_built_table(hand_built):
    text = dp.report(hand_built)
    first, second = text.split("\n\n")
    assert first.startswith("jit_train_step(11): 2 run(s), median 75.000 ms")
    rows = {line.split()[0]: line.split()[1:] for line in first.splitlines()[2:]}
    # median of two runs' capture time (20 and 0 ms), and the rate over both
    assert rows["kfac_capture"] == ["10.000", "13.33", "100.0", "5.0", "0"]  # 2 GFLOP, 0.1 GB in 20 ms
    assert "kfac_exchange" not in rows and "largest" in rows
    assert "jit_train_step(22): 1 run(s)" in second


def test_self_time_and_leaves():
    got = dp.self_and_leaf([(0, 10, "w"), (1, 4, "a"), (5, 9, "b"), (12, 13, "c")])
    assert sorted(got) == [("a", 3, True), ("b", 4, True), ("c", 1, True), ("w", 3, False)]


def test_find_xplane(tmp_path, hand_built):
    assert dp.find_xplane(hand_built).endswith("host.xplane.pb")
    assert dp.find_xplane(RECORDED) == RECORDED
    with pytest.raises(FileNotFoundError):
        dp.find_xplane(str(tmp_path / "nothing"))


def test_a_cut_file_is_an_error(tmp_path):
    cut = tmp_path / "cut.xplane.pb"
    with open(RECORDED, "rb") as f:
        cut.write_bytes(f.read(100_000))
    with pytest.raises(ValueError):
        dp.read_xspace(str(cut))


# -- the recorded traces ----------------------------------------------------


def _summary(planes):
    """What the decoder is held to: planes, lines, event counts, and the
    device plane's op metadata with the stats the reduction reads."""
    out = {"planes": [[p["name"], [[l["name"], l["n_events"], l["timestamp_ns"]] for l in p["lines"]]]
                      for p in planes]}
    device = next(p for p in planes if p["name"] == "/device:TPU:0")
    out["metadata"] = {
        str(k): [md["name"], md["stats"].get("tf_op"), md["stats"].get("program_id"),
                 md["stats"].get("flops"), md["stats"].get("bytes_accessed"), md["stats"].get("hlo_category")]
        for k, md in sorted(device["event_metadata"].items())}
    ops = next(l for l in device["lines"] if l["name"] == dp.OPS_LINE)
    out["ops"] = [list(ev) for ev in ops["events"]]
    return out


_WITH_PB2 = r"""
import json, sys
from tensorflow.tsl.profiler.protobuf import xplane_pb2
space = xplane_pb2.XSpace()
space.ParseFromString(open(sys.argv[1], "rb").read())
out = {"planes": [[p.name, [[l.name, len(l.events), l.timestamp_ns] for l in p.lines]] for p in space.planes]}
device = next(p for p in space.planes if p.name == "/device:TPU:0")
names = {k: v.name for k, v in device.stat_metadata.items()}
def value(s):
    kind = s.WhichOneof("value")
    return names.get(s.ref_value, "") if kind == "ref_value" else getattr(s, kind)
md = {}
for k, m in sorted(device.event_metadata.items()):
    stats = {names[s.metadata_id]: value(s) for s in m.stats}
    md[str(k)] = [m.name] + [stats.get(n) for n in ("tf_op", "program_id", "flops", "bytes_accessed", "hlo_category")]
out["metadata"] = md
ops = next(l for l in device.lines if l.name == "XLA Ops")
out["ops"] = [[e.metadata_id, e.offset_ps, e.duration_ps] for e in ops.events]
json.dump(out, sys.stdout)
"""


@pytest.mark.parametrize("path", [RECORDED, RECORDED_PHASES], ids=["recorded", "recorded_phases"])
def test_wire_decoder_reads_what_xplane_pb2_reads(path):
    assert os.path.isfile(path)
    try:
        done = subprocess.run([sys.executable, "-c", _WITH_PB2, path], capture_output=True, text=True,
                              timeout=300, env={**os.environ, "TF_CPP_MIN_LOG_LEVEL": "3", "JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        pytest.skip("importing tensorflow took over 300 s here")
    if done.returncode != 0:
        pytest.skip("tensorflow's xplane_pb2 does not import here: " + done.stderr.strip()[-200:])
    theirs = json.loads(done.stdout)
    ours = _summary(dp.read_xspace(path))
    assert ours["planes"] == theirs["planes"]
    assert ours["metadata"] == theirs["metadata"]
    assert ours["ops"] == theirs["ops"]


def test_recorded_trace_committed_totals():
    planes = dp.read_xspace(RECORDED)
    assert [p["name"] for p in planes] == [
        "#Chip0 Host Interface", "/device:TPU:0", "#Chip0 Misc", "/host:metadata",
        "/device:CUSTOM:Megascale Trace", "/host:CPU", "Task Environment"]
    device = planes[1]
    assert [(l["name"], l["n_events"]) for l in device["lines"]] == [
        ("Scalar Unit", 0), ("Steps", 3), ("XLA Modules", 3), ("XLA Ops", 1858),
        ("Async XLA Ops", 424), ("TC Overlay", 0)]
    assert len(device["event_metadata"]) == 1579
    with_name = [md for md in device["event_metadata"].values() if "tf_op" in md["stats"]]
    assert len(with_name) == 696
    assert {md["stats"]["program_id"] for md in with_name} == {10690149298037377328, 1592516842482302765}
    assert sum(md["stats"]["flops"] for md in device["event_metadata"].values() if "flops" in md["stats"]) == 3506245210
    # recorded before the program had scopes: every op reads unscoped
    runs = dp.program_runs(RECORDED)
    assert [r["program"] for r in runs] == [
        "jit_train_step(10690149298037377328)", "jit_train_step(1592516842482302765)",
        "jit_train_step(10690149298037377328)"]
    assert [r["duration_ps"] for r in runs] == [87043750, 648751172, 88350000]
    assert all(r["phases"][p]["ps"] == 0 for r in runs for p in PHASES)
    assert [r["phases"][dp.UNSCOPED]["ps"] for r in runs] == [68183972, 622644994, 69047656]


def test_recorded_phases_trace_committed_totals():
    """The tiny LM cell with the scopes, recorded on a v5e (PR 25): factors,
    refresh, factors in the traced stretch, then two runs of the plain
    program, the twin's state made by a jitted lambda, two runs of the twin."""
    runs = dp.program_runs(RECORDED_PHASES)
    assert [(r["program"], r["duration_ps"]) for r in runs] == [
        ("jit_train_step(10690149298037377328)", 87558750),
        ("jit_train_step(1592516842482302765)", 649081250),
        ("jit_train_step(10690149298037377328)", 88916250),
        ("jit_train_step(16575416048803303545)", 70551250),
        ("jit_train_step(16575416048803303545)", 69067500),
        ("jit__lambda(1925656306876129121)", 20261328),
        ("jit_train_step(2453878947520227402)", 55816328),
        ("jit_train_step(2453878947520227402)", 55547500)]
    nonzero = lambda r: {p: c["ps"] for p, c in r["phases"].items() if c["ps"]}
    assert nonzero(runs[0]) == {
        "model": 25680624, "grad_clip": 7596328, "kfac_capture": 8563438, "kfac_apply": 5838358,
        "optimizer": 713750, dp.UNSCOPED: 20603360, dp.IDLE: 18562892}
    assert nonzero(runs[1]) == {
        "model": 25713046, "grad_clip": 7409532, "kfac_capture": 12593828, "kfac_refresh": 536677030,
        "kfac_apply": 5930078, "optimizer": 692656, dp.UNSCOPED: 33954136, dp.IDLE: 26110944}
    assert runs[1]["phases"]["kfac_refresh"] == {"ps": 536677030, "flops": 1788474286, "bytes": 53068898, "ops": 192}
    assert runs[1]["phases"]["kfac_capture"]["flops"] == 584088348
    # each program reads as its kind: plain applies and captures nothing, the twin has no K-FAC op
    assert set(nonzero(runs[3])) == {"model", "grad_clip", "kfac_apply", "optimizer", dp.UNSCOPED, dp.IDLE}
    assert set(nonzero(runs[6])) == {"model", "grad_clip", "optimizer", dp.UNSCOPED, dp.IDLE}
    assert set(nonzero(runs[5])) == {dp.UNSCOPED, dp.IDLE}  # not a step program: no scope
    for r in runs:
        assert sum(c["ps"] for c in r["phases"].values()) == r["duration_ps"]


def test_command_line_prints_a_table_per_program(capsys):
    assert dp.main([RECORDED]) == 0
    out = capsys.readouterr().out
    assert out.count("run(s), median") == 2 and "GFLOP/s" in out
    assert dp.main([]) == 2


def test_command_line_runs_as_a_module():
    done = subprocess.run(
        [sys.executable, "-m", "kfac_pytorch_tpu.observability.device_phases", RECORDED],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr
    assert "jit_train_step(1592516842482302765): 1 run(s)" in done.stdout
    assert "tensorflow" not in done.stderr


# -- the program's own use ---------------------------------------------------


def test_spans_lie_on_the_host_plane_of_the_trace_and_the_epoch_prints_its_table(tmp_path, capsys):
    """With telemetry on, a span is a ``TraceAnnotation`` too: under
    ``maybe_trace`` its name is on ``/host:CPU`` of the same ``.xplane.pb``
    as the device ops. On the CPU there is no TPU plane: the epoch's report
    says so and raises nothing."""
    import jax.numpy as jnp

    from kfac_pytorch_tpu.observability.telemetry import Telemetry
    from kfac_pytorch_tpu.training.profiling import maybe_trace

    tel = Telemetry(enabled=True)
    with maybe_trace(str(tmp_path), True):
        with tel.span("step/plain") as sp:
            sp.block(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert len(tel.hists["step/plain"]) == 1
    assert "no program run on a /device:TPU plane" in capsys.readouterr().out
    planes = dp.read_xspace(dp.find_xplane(str(tmp_path)), want_plane=lambda name: name == "/host:CPU")
    host = next(p for p in planes if p["name"] == "/host:CPU")
    assert "step/plain" in {md["name"] for md in host["event_metadata"].values()}
    # the disabled path stays the shared no-op: no annotation, no allocation
    from kfac_pytorch_tpu.observability.telemetry import _NULL_SPAN

    assert Telemetry(enabled=False).span("step/plain") is _NULL_SPAN


def test_maybe_trace_off_is_a_no_op(tmp_path):
    from kfac_pytorch_tpu.training.profiling import maybe_trace

    with maybe_trace(str(tmp_path), False):
        pass
    assert not os.listdir(tmp_path)
