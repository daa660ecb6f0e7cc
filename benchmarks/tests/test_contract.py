"""``BENCHMARK.json`` against the form the driver checks before any run, and
the data-driven layout: every name in it has its files."""

import json
import os
import re

import pytest

import run as bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = bench.load_json(bench.ROOT, "BENCHMARK.json")


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(bench.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert 1 <= len(B["command"]) <= 32 and all(line(w) for w in B["command"])
    assert B["paths"] == ["benchmarks"]


def test_configs():
    names = [c["name"] for c in B["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in B["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/") and c["name"] in used
        cfg = bench.load_json(bench.ROOT, c["file"])
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for part in ("builder", "work", "reference"):
            folder = "configs" if part == "builder" else part
            assert os.path.isfile(os.path.join(bench.HERE, folder, cfg[part] + ".py"))


def test_workloads_have_their_files():
    names = [w["name"] for w in B["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, len(names) // 4)
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        cell = bench.load_cell(w["name"])
        limits = cell["file"]["limits"]
        assert limits["window_compiles"] == 0 and limits["nonfinite_losses"] == 0
        assert cell["file"]["warmup_steps"] >= bench.CHECKED_STEPS
        assert set(cell["file"]["traced_kinds"]) <= {"plain", "factors", "refresh"}


def recorded_readings():
    folder = os.path.join(bench.HERE, "tests", "readings")
    return sorted(f[:-6] for f in os.listdir(folder) if f.endswith(".jsonl"))


@pytest.mark.parametrize("name", recorded_readings())
def test_limits_separate_the_chip_readings(name):
    """The readings the limits were set from (taken on the chip at the cell's
    own size, ``chip_readings.py``) through the harness's own comparison under
    the limits as committed: the program's come out correct, every control's
    and fault's not."""
    import chip_readings

    cell = bench.load_cell(name)
    path = os.path.join(bench.HERE, "tests", "readings", name + ".jsonl")
    kinds = {json.loads(text)["kind"] for text in open(path)}
    assert "program" in kinds and len(kinds) > 1
    assert chip_readings.decide_recorded(cell, path) == 0


def test_metrics():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(B["end_to_end"]) <= 16
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    names = [m["name"] for m in B["per_layer"]]
    assert len(set(names)) == len(names) and not set(names) & set(e2e)
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        reader = bench.metric_reader(m["name"])
        moves = reader.MOVES[m["name"]] if isinstance(reader.MOVES, dict) else reader.MOVES
        assert reader.LAYER == m["layer"] and moves == m["moves"] and callable(reader.read)
    # every cell reports at least one per-layer metric and the whole step's share of the peak
    assert any("mfu" in re.split(r"[_.]", n) for n in names)


SCOPE_LINES = {"fwd_bwd_scope_ms": "step builder", "capture_scope_ms": "capture", "capture_scope_roofline": "capture",
               "apply_scope_ms": "apply", "apply_scope_roofline": "apply", "refresh_scope_ms": "refresh",
               "optimizer_scope_ms": "step builder", "unscoped_pct": "whole step"}


def test_the_lines_read_from_the_programs_phases():
    by_name = {m["name"]: m for m in B["per_layer"]}
    for name, layer in SCOPE_LINES.items():
        m = by_name[name]
        assert m["source"] == "program_span" and m["layer"] == layer
        assert m["unit"] == ("%" if name.endswith(("_roofline", "_pct")) else "ms")
        assert m["better"] == ("higher" if name.endswith("_roofline") else "lower")
    # a refresh step is the tail of the first cell and rare in the amortized one: the quantity is split
    assert by_name["refresh_scope_ms"]["workloads"] == by_name["refresh_extra_ms.tail"]["workloads"] == ["gpt2s_t1024_f1_k10"]
    assert by_name["refresh_extra_ms.rare"]["workloads"] == ["gpt2s_t1024_f10_k100"]
    assert by_name["refresh_extra_ms.rare"]["moves"] == "samples_per_s"
    # the lines by difference of programs stay beside them, under their names
    assert {"capture_extra_ms", "capture_roofline", "apply_extra_ms", "apply_roofline"} <= set(by_name)
    assert not any(w["chips"] == 4 for w in B["workloads"])


def test_a_traced_stretch_runs_every_kind_of_its_schedule_twice():
    for w in B["workloads"]:
        cell = bench.load_cell(w["name"])
        mix, kinds = cell["traffic_mix"], cell["file"]["traced_kinds"]
        scheduled = {"refresh", "factors"} | ({"plain"} if mix["fac_update_freq"] > 1 else set())
        assert {k: kinds.count(k) >= 2 for k in scheduled} == dict.fromkeys(scheduled, True)
        # warm-up compiles every program of the window: it has to reach the first capture step after step 0
        assert cell["file"]["warmup_steps"] > (mix["fac_update_freq"] if mix["fac_update_freq"] > 1 else 1)
