"""``trace_phases.py``: the program's phases by run and kind on the trace
recorded on a v5e from the scoped tiny LM cell
(``recorded_v5e_phases.xplane.pb``: factors, refresh, factors, two plain and
two twin steps), nothing and no exception on the recording of a program
without scopes (``recorded_v5e.xplane.pb``, the parent of PR 25) and where the
program has no reader; the choice of the kind a reader takes; and the eight
readers of ``metrics/`` over a hand-made run."""

import os
import sys

import pytest

import run as bench

tp = bench.load_module(bench.HERE, "trace_phases.py")
TESTS = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(TESTS, "recorded_v5e_phases.xplane.pb")
UNSCOPED = os.path.join(TESTS, "recorded_v5e.xplane.pb")
DISPATCHED = ["factors", "refresh", "factors", "plain", "plain", "twin", "twin"]


def test_recorded_scoped_trace_by_kind():
    by = tp.by_kind(SCOPED, DISPATCHED)
    assert {k: len(v) for k, v in by.items()} == {"factors": 2, "refresh": 1, "plain": 2, "twin": 2}
    from kfac_pytorch_tpu.observability.phases import PHASES

    for runs in by.values():
        for phases in runs:
            assert set(phases) == set(PHASES) | {"unscoped", "idle"} and all(ms >= 0 for ms in phases.values())
    assert by["refresh"][0]["kfac_refresh"] > 0.5 > by["factors"][0]["kfac_refresh"] == 0
    assert by["plain"][0]["kfac_capture"] == 0 < by["plain"][0]["kfac_apply"]
    assert not any(ms for p, ms in by["twin"][0].items() if p.startswith("kfac_"))
    # the phases and the idle gaps of a run add up to its time on the device
    reduce = bench.load_module(bench.HERE, "trace_reduce.py")
    ms = reduce.seconds_by_kind(reduce.reduce(reduce.read_planes(SCOPED), 1)["module_runs"], DISPATCHED)
    for kind, runs in by.items():
        assert [sum(p.values()) for p in runs] == pytest.approx([sec * 1e3 for sec in ms[kind]], rel=1e-4)  # ProfileData rounds to nanoseconds


@pytest.mark.parametrize("dispatched", [
    DISPATCHED[:-1],  # a run too many in the trace
    ["factors", "factors", "refresh", "plain", "plain", "twin", "twin"],  # one program, two kinds
    ["plain", "refresh", "plain", "factors", "factors", "twin", "twin"],  # a plain run that captures
    ["factors", "refresh", "factors", "twin", "twin", "plain", "plain"],  # a twin's run with kfac_apply
])
def test_runs_that_contradict_the_dispatch_give_nothing(dispatched):
    assert tp.by_kind(SCOPED, dispatched) == {}


def test_a_program_without_scopes_gives_nothing_and_raises_nothing():
    assert tp.by_kind(UNSCOPED, ["factors", "refresh", "factors"]) == {}


def test_a_program_without_the_reader_gives_nothing(monkeypatch):
    monkeypatch.setitem(sys.modules, "kfac_pytorch_tpu.observability.device_phases", None)  # import fails
    assert tp.by_kind(SCOPED, DISPATCHED) == {}


def hand_made_run(window_kinds):
    phases = lambda **kw: {"model": 100.0, "grad_clip": 1.0, "optimizer": 2.0, "kfac_apply": 10.0,
                           "kfac_capture": 0.0, "kfac_refresh": 0.0, "unscoped": 5.0, "idle": 0.5, **kw}
    run = {
        "records": [{"kind": k} for k in window_kinds],
        "phase_ms": {
            "plain": [phases(), phases(model=102.0), phases(model=104.0)],
            "factors": [phases(model=110.0, kfac_capture=50.0), phases(model=112.0, kfac_capture=54.0)],
            "refresh": [phases(model=111.0, kfac_capture=51.0, kfac_refresh=170.0, unscoped=60.0)],
            "twin": [phases(model=99.0, kfac_apply=0.0)],
        },
        "work": {"capture": {"flops": 197e12 * 0.013, "bytes": 0}, "apply": {"flops": 0, "bytes": 819e9 * 0.002}},
        "least_seconds": lambda w: bench.load_module(bench.HERE, "work", "common.py").least_seconds(
            w, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}),
    }
    run["phase_median_ms"] = lambda names, **how: tp.median_ms(run, names, **how)
    run["read"] = lambda name: bench.metric_reader(name).read(run)
    return run


def test_the_kind_a_reader_takes():
    mostly_plain = hand_made_run(["plain"] * 90 + ["factors"] * 9 + ["refresh"])
    assert tp.median_ms(mostly_plain, ("model",)) == 102.0  # the kind the window ran most
    assert tp.median_ms(mostly_plain, ("kfac_capture",)) == 52.0  # ... among those that hold the phase
    assert tp.median_ms(mostly_plain, ("kfac_refresh",)) == 170.0
    assert tp.median_ms(mostly_plain, ("kfac_exchange",)) is None  # no run holds it
    assert tp.median_ms(mostly_plain, ("model",), kind="eigen") is None  # no such run in the trace
    mostly_factors = hand_made_run(["factors"] * 9 + ["refresh"])
    assert tp.median_ms(mostly_factors, ("model",)) == 111.0
    assert tp.median_ms(mostly_factors, ("optimizer", "grad_clip")) == 3.0
    assert tp.median_ms({"records": [], "phase_ms": {}}, ("model",)) is None


def test_the_eight_readers():
    run = hand_made_run(["factors"] * 9 + ["refresh"])
    got = {name: run["read"](name) for name in (
        "fwd_bwd_scope_ms", "capture_scope_ms", "capture_scope_roofline", "apply_scope_ms",
        "apply_scope_roofline", "refresh_scope_ms", "optimizer_scope_ms", "unscoped_pct")}
    assert got == pytest.approx({
        "fwd_bwd_scope_ms": 111.0, "capture_scope_ms": 52.0, "capture_scope_roofline": 25.0,
        "apply_scope_ms": 10.0, "apply_scope_roofline": 20.0, "refresh_scope_ms": 170.0,
        "optimizer_scope_ms": 3.0, "unscoped_pct": 100 * (5.0 / 178.5 + 5.0 / 184.5) / 2})
    nothing = {**run, "phase_ms": {}}
    nothing["phase_median_ms"] = lambda names, **how: tp.median_ms(nothing, names, **how)
    nothing["read"] = lambda name: bench.metric_reader(name).read(nothing)
    assert all(nothing["read"](name) is None for name in got)  # nothing to read: left out, never 0


def test_window_seconds_are_of_the_listed_runs():
    by = tp.by_kind(SCOPED, DISPATCHED)
    got = dict(tp.window_seconds(by, ["factors", "refresh", "factors"]))
    assert got["kfac_refresh"] == pytest.approx(by["refresh"][0]["kfac_refresh"] * 1e-3)
    assert got["model"] == pytest.approx(sum(p["model"] for p in by["factors"] + by["refresh"]) * 1e-3)
    assert "kfac_exchange" not in got and len(got) <= 10
    assert tp.window_seconds({}, ["factors"]) == []
