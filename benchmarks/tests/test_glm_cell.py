"""The expert-bank configuration's files: the whole command on the CPU at a
tiny ``glm4_moe_lite`` cell (reference in layer groups, the builder through
the LM trainer's ``build()``, the routing counters in the records), its
control and a planted fault; ``work/glm_moe_lite.py`` against hand counts;
the new per-layer readers on a synthetic run. No device number comes of a
CPU run."""

import os

import jax
import jax.numpy as jnp
import pytest

import run as bench

TESTS = os.path.dirname(os.path.abspath(__file__))
PEAK = bench.load_json(bench.HERE, "peaks.json")["devices"]["TPU v5 lite"]
CELL = "glm47f_ep8_f1_k10"


def full_cell():
    """The cell at its real size, as ``BENCHMARK.json`` has it."""
    return bench.load_cell(CELL)


def tiny_cell():
    return bench.load_cell("glm_tiny_t64", benchmark=bench.load_json(TESTS, "benchmark_tiny_glm.json"), base=TESTS)


def run_tiny(seed=3, **kw):
    return bench.run_cell(tiny_cell(), seed, 1.0, False, jax.devices()[:1], PEAK, **kw)


def test_rehearsal_is_correct_with_counters_in_its_records():
    result = run_tiny(seed=2**31 + 21)
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    assert result["info"]["steps_by_kind"].keys() == {"factors", "refresh"}
    assert {"grad1_median_leaf", "delta3_median_leaf", "grad1_worst_leaf"} <= result["checks"].keys()


def second_half_repeats_the_first(step):
    def broken(state, batch, *args, **flags):
        batch = jax.tree_util.tree_map(lambda a: jnp.concatenate([a[:, : a.shape[1] // 2]] * 2, axis=1), batch)
        return step(state, batch, *args, **flags)
    return broken


def test_planted_fault_is_not_correct():
    result = run_tiny(break_step=second_half_repeats_the_first)
    assert result["correct"] is False
    assert [n for n, row in result["checks"].items() if row["value"] > row["limit"]]


def test_control_in_bfloat16_is_not_correct_and_routes_some_tokens_elsewhere():
    cell = tiny_cell()
    check = bench.load_module(bench.HERE, "check.py")
    traffic = bench.load_module(bench.HERE, "traffic.py")
    program = bench.Program(cell, jax.devices()[:1])
    _, p0 = program.start(9)
    pool = traffic.make_pool(cell["traffic_mix"], cell["cfg"], 1, 9)
    ref = bench.run_reference(cell, p0, pool, float(program.lr))
    control = bench.run_reference(cell, p0, pool, float(program.lr), precision="bfloat16")
    values, _ = check.readings(control, ref)
    correct, rows = check.decide(values, {k: v for k, v in cell["file"]["limits"].items() if k in values})
    assert correct is False, rows


def test_records_carry_the_routing_counters():
    cell = tiny_cell()
    traffic = bench.load_module(bench.HERE, "traffic.py")
    program = bench.Program(cell, jax.devices()[:1])
    pool = traffic.make_pool(cell["traffic_mix"], cell["cfg"], 1, 4)
    state, p0 = program.start(4)
    _, records, _ = program.first_steps(state, p0, traffic.feed(pool), bench.CHECKED_STEPS)
    for record in records:
        assert {"loss", "moe_held_rows", "moe_load_max_over_mean", "moe_dropped_rows"} <= set(record["counters"])
        assert record["counters"]["moe_dropped_rows"] == 0.0
    reader = bench.metric_reader("moe_load_max_over_mean")
    assert 1.0 <= reader.read({"records": records}) <= 4.0
    assert reader.read({"records": [{"counters": {"loss": 1.0}}]}) is None  # a program with no such counter


def full_work():
    cell = full_cell()
    w = bench.load_module(bench.HERE, "work", "glm_moe_lite.py").work(cell["cfg"], cell["traffic_mix"], 1)
    w.update(bench.load_module(bench.HERE, "work", "common.py").kfac_work(w["layers"]))
    return cell, w


def test_work_against_hand_counts():
    cell, w = full_work()
    attention = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048  # 21,757,952
    expert = 3 * 2048 * 1536
    params = 5 * attention + 3 * 2048 * 10240 + 4 * (2048 * 64 + expert + 0.5 * expert) + 2048 * 19360
    assert attention == 21_757_952 and w["matmul_params"] == params == 268_500_992
    t = cell["traffic_mix"]["seq_len"]
    assert t == 2048  # the retreat from the 4096 of the traffic's name (PERF.md section 6, PR 28)
    pairs = 2 * 20 * 512 * (t + 1) / 2  # QK^T and PV over (T + 1) / 2 keys, 20 heads of 256 + 256
    assert w["forward_flops_per_sample"] == t * (2 * params + 5 * pairs)
    assert w["model_flops_per_sample"] == 3 * w["forward_flops_per_sample"]
    # K-FAC layers after the retreat: q_a, kv_a, router and 2 x 8 experts' gate / up of the 4 expert layers
    assert cell["cfg"]["kfac"]["exclude"] == ["dense_layers", "shared_expert", "down_banks"]
    assert len(w["layers"]) == 4 * (2 + 1 + 16)
    bank = next(l for l in w["layers"] if l["name"] == "layer1.up.7")
    assert bank == {"name": "layer1.up.7", "a_side": 2048, "g_side": 1536, "rows": t * 4 // 64,
                    "in_elems": 128 * 2048, "out_elems": 128 * 1536}
    assert not any(l["name"].startswith("layer0.") or "shared" in l["name"] or ".down." in l["name"]
                   for l in w["layers"])
    assert w["experts"]["flops"] == 4 * 3 * 2 * (t // 2) * expert
    assert w["attention"]["flops"] == 5 * 2 * (20 * t * (t + 1) / 2) * 7 * 256
    # with every group under K-FAC (the issue's list) the layers are 122 and the inverses 244
    whole = {**cell["cfg"], "kfac": {**cell["cfg"]["kfac"], "exclude": []}}
    full = bench.load_module(bench.HERE, "work", "glm_moe_lite.py").work(whole, cell["traffic_mix"], 1)
    assert len(full["layers"]) == 5 * 2 + 4 * (1 + 3 + 24)
    assert next(l for l in full["layers"] if l["name"] == "layer1.down.7")["a_side"] == 1536


def test_new_readers_on_a_synthetic_run():
    cell, w = full_work()
    common = bench.load_module(bench.HERE, "work", "common.py")
    trace_phases = bench.load_module(bench.HERE, "trace_phases.py")
    phases = {"model": 200.0, "attention": 60.0, "moe_route": 5.0, "moe_experts": 12.0, "kfac_capture": 90.0,
              "kfac_apply": 50.0, "unscoped": 3.0}
    run = {"records": [{"kind": "factors"}] * 9 + [{"kind": "refresh"}], "work": w,
           "phase_ms": {"factors": [phases, phases], "refresh": [dict(phases, kfac_refresh=400.0)]},
           "device_ms": {"factors": [420.0, 420.0], "refresh": [900.0, 910.0]},
           "least_seconds": lambda work: common.least_seconds(work, PEAK, 1)}
    run["phase_median_ms"] = lambda names, **how: trace_phases.median_ms(run, names, **how)
    memo = {}
    run["read"] = lambda name: memo.setdefault(name, bench.metric_reader(name).read(run))
    assert run["read"]("route_scope_ms") == 5.0 and run["read"]("experts_scope_ms") == 12.0
    assert run["read"]("attention_scope_ms") == 60.0
    assert run["read"]("refresh_scope_ms") == 400.0
    assert run["read"]("refresh_extra_ms.tail") == run["read"]("refresh_extra_ms.banks") == 485.0
    assert run["read"]("refresh_scope_ms.banks") == 400.0
    assert 0 < run["read"]("experts_roofline") < 100 and 0 < run["read"]("attention_roofline") < 100
    # a program without the phases (the parent's): nothing, and no exception
    run["phase_ms"] = {"factors": [{"model": 300.0, "kfac_capture": 90.0}]}
    memo.clear()
    for name in ("route_scope_ms", "experts_scope_ms", "experts_roofline", "attention_scope_ms", "attention_roofline"):
        assert run["read"](name) is None
    run["work"] = {}
    run["phase_ms"] = {"factors": [phases]}
    memo.clear()
    assert run["read"]("experts_roofline") is None


def test_the_recorded_control_is_not_separated_and_the_limits_say_what_they_hold():
    """The finding of PR 28, kept where a later reader of the limits meets it:
    on the chip the reference in bfloat16 reads like the program (within 2.5
    times by every number), so the precision hardly moves this cell's numbers
    and its limits lie between the program's largest reading and 1, what a
    state left unchanged reads: they fail the planted fault, not a lower
    precision. A check that replays the program's routing in the reference
    is the next ``benchmark`` issue."""
    import json

    rows = lambda *parts: [json.loads(t) for t in open(os.path.join(TESTS, *parts))]
    readings = rows("readings", CELL + ".jsonl")
    control = rows("readings_not_separated", CELL + ".control_bf16.jsonl")
    limits = full_cell()["file"]["limits"]
    assert len([r for r in readings if r["kind"] == "program"]) >= 12 and len(control) == 3
    for name in ("grad1_median_leaf", "delta3_median_leaf", "grad1_worst_leaf", "delta3_worst_leaf"):
        program = max(r.get(name, 0.0) for r in readings if r["kind"] == "program")  # a whole run's line holds its limits' numbers alone
        fault = min(r[name] for r in readings if r["kind"] == "half_batch")
        assert min(r[name] for r in control) < 2.5 * program  # no separation
        if name in limits:
            assert 4 * program < limits[name] < fault / 4 and limits[name] < 0.1  # far under 1, an unchanged state's reading
    assert "grad1_worst_leaf" not in limits and "NOT A LOWER PRECISION" in full_cell()["file"]["limits_from"]


def test_the_entries_of_the_benchmark():
    b = bench.load_json(bench.ROOT, "BENCHMARK.json")
    cfg = bench.load_json(bench.ROOT, "benchmarks", "configs", "glm47_flash_ep8.json")
    entry = next(c for c in b["configs"] if c["name"] == "glm47_flash_ep8")
    assert entry["file"] == "benchmarks/configs/glm47_flash_ep8.json" and entry["source"] == cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size",
                                                  "num_nextn_predict_layers"]
    assert cfg["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64, "vocab_size": 154880,
                                "num_nextn_predict_layers": 1}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] and cfg["held_experts"] == [0, 8]
    assert [w["name"] for w in b["workloads"] if w["config"] == "glm47_flash_ep8"] == [CELL]
    cell = b["workloads"][-1]
    assert cell == {**cell, "name": CELL, "traffic": "t4096_b1_f1_k10", "chips": 1}
    mine = [m["name"] for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["route_scope_ms", "experts_scope_ms", "experts_roofline", "attention_scope_ms",
                    "attention_roofline", "refresh_scope_ms.banks", "refresh_extra_ms.banks",
                    "moe_load_max_over_mean"]
    assert b["per_layer"][-len(mine):] == [m for m in b["per_layer"] if m["name"] in mine]  # at the end of the list
    # a refresh step is the tail here as in the first cell; the accepted lines' lists are not this PR's to append to
    assert not any(CELL in m.get("workloads", []) for m in b["per_layer"] if m["name"] not in mine)
    assert bench.metric_reader("refresh_scope_ms.banks").MOVES == bench.metric_reader("refresh_extra_ms.banks").MOVES == "step_p95_ms"
