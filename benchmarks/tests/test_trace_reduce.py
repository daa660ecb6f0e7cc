"""``trace_reduce.py``: the interval arithmetic on hand-made planes, and the
whole reduction on one small trace recorded on the chip
(``recorded_v5e.xplane.pb``, a few steps of the tiny LM cell on a v5e)."""

import os

import pytest

import run as bench

tr = bench.load_module(bench.HERE, "trace_reduce.py")
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded_v5e.xplane.pb")


def test_union_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_self_times_do_not_count_a_loop_body_twice():
    s = 1_000_000_000
    events = [(0, 10 * s, "while"), (1 * s, 4 * s, "fusion.1"), (5 * s, 9 * s, "fusion.2"), (12 * s, 13 * s, "copy")]
    got = tr.self_times(events)
    assert got == {"while": 3.0, "fusion.1": 3.0, "fusion.2": 4.0, "copy": 1.0}


def test_reduce_on_hand_made_planes():
    ms = 1_000_000
    ops = [(0, 40 * ms, "fusion"), (50 * ms, 60 * ms, "all-reduce.1"), (55 * ms, 70 * ms, "fusion"),
           (90 * ms, 100 * ms, "cholesky")]
    host = [(0, 45 * ms, "dispatch"), (45 * ms, 52 * ms, "put_global_batch"), (70 * ms, 100 * ms, "metric_fetch")]
    got = tr.reduce({"devices": {0: {"ops": ops, "modules": [(0, 100 * ms, "jit_train_step")]}}, "host": host}, 1)
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.070)  # 40 + 20 (50..70) + 10
    assert got["collective_s"] == pytest.approx(0.010)
    assert got["collective_exposed_s"] == pytest.approx(0.005)  # 50..55: no other op runs
    assert dict(got["idle_gaps"]) == pytest.approx({"dispatch": 0.005, "put_global_batch": 0.005, "metric_fetch": 0.020})
    assert got["top_ops"][0] == ["fusion", pytest.approx(0.055)]
    assert got["modules"] == ["jit_train_step"]


def test_window_span_bounds_busy_and_idle():
    ms = 1_000_000
    ops = [(0, 40 * ms, "fusion"), (60 * ms, 90 * ms, "fusion")]
    host = [(0, 50 * ms, "dispatch"), (50 * ms, 100 * ms, "metric_fetch")]
    planes = {"devices": {0: {"ops": ops, "modules": []}}, "host": host, "window": [(10 * ms, 70 * ms)]}
    got = tr.reduce(planes, 1)
    assert got["window_s"] == pytest.approx(0.060)
    assert got["busy_s"] == pytest.approx(0.040)  # 10..40 and 60..70
    assert dict(got["idle_gaps"]) == pytest.approx({"dispatch": 0.010, "metric_fetch": 0.010})


def test_seconds_by_kind_matches_program_runs_in_order():
    runs = [["jit_train_step(1)", 0.5], ["jit_convert(9)", 0.001], ["jit_train_step(2)", 0.1],
            ["jit_train_step(2)", 0.11], ["jit_train_step(3)", 0.09]]
    got = tr.seconds_by_kind(runs, ["refresh", "plain", "plain", "twin"])
    assert got == {"refresh": [0.5], "plain": [0.1, 0.11], "twin": [0.09]}
    assert tr.seconds_by_kind(runs, ["refresh", "plain", "plain"]) == {}  # a run too many
    assert tr.seconds_by_kind(runs, ["refresh", "plain", "factors", "twin"]) == {}  # one program, two kinds
    assert tr.seconds_by_kind(runs, ["plain", "refresh", "refresh", "plain"]) == {}  # one kind, two programs


def test_no_device_operation_is_an_error():
    with pytest.raises(RuntimeError):
        tr.reduce({"devices": {}, "host": [(0, 1, "dispatch")]}, 1)


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace in this checkout")
def test_recorded_v5e_trace():
    planes = tr.read_planes(RECORDED)
    assert 0 in planes["devices"] and planes["devices"][0]["ops"]
    assert {name for _, _, name in planes["host"]} == set(tr.HOST_SPANS)
    got = tr.reduce(planes, 1)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["collective_s"] == 0  # one chip: no collective
    assert abs(sum(v for _, v in got["idle_gaps"]) - (got["window_s"] - got["busy_s"])) < 1e-6
    assert any("jit_train_step" in m for m in got["modules"])
    # the recording holds the tiny LM cell's steps: program runs in order, each with its device time
    by = tr.seconds_by_kind(got["module_runs"], ["factors", "refresh", "factors"])
    assert set(by) == {"factors", "refresh"} and all(sec > 0 for v in by.values() for sec in v)
    assert min(by["refresh"]) > max(by["factors"])
