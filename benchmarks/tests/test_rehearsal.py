"""The whole command on the CPU at a tiny configuration (not in
``BENCHMARK.json``), past the harness's look for a chip; the faults the
cells can have, planted under the timed path, and the controls (the
reference in bfloat16 for the LM, the trainer's own ``--bf16`` for the conv
net), each of which has to come out as not correct. A CPU run gives no device number: the tests
read ``correct`` and the checks, never a time."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import run as bench

TESTS = os.path.dirname(os.path.abspath(__file__))
PEAK = bench.load_json(bench.HERE, "peaks.json")["devices"]["TPU v5 lite"]


def tiny_cell(name):
    return bench.load_cell(name, benchmark=bench.load_json(TESTS, "benchmark_tiny.json"), base=TESTS)


def run_tiny(name="lm_tiny_t32", seed=3, **kw):
    return bench.run_cell(tiny_cell(name), seed, 1.0, False, jax.devices()[:1], PEAK, **kw)


def test_rehearsal_is_correct_and_well_formed():
    result = run_tiny(seed=2**31 + 11)  # a seed past 32 signed bits
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[-1] == "checks"  # the numbers compared come last
    assert set(result["metrics"]) == {"samples_per_s", "step_p95_ms", "peak_hbm_gib", "setup_s"}
    assert result["attempted"] % tiny_cell("lm_tiny_t32")["traffic_mix"]["kfac_update_freq"] == 0
    assert result["info"]["steps_by_kind"].keys() == {"factors", "refresh"}
    json.dumps(result)


def test_same_seed_same_readings():
    a, b = run_tiny(seed=5), run_tiny(seed=5)
    assert a["checks"] == b["checks"]
    assert run_tiny(seed=6)["checks"] != a["checks"]


def test_records_carry_every_scalar_of_the_steps_metrics():
    cell = tiny_cell("lm_tiny_t32")
    traffic = bench.load_module(bench.HERE, "traffic.py")
    program = bench.Program(cell, jax.devices()[:1])
    pool = traffic.make_pool(cell["traffic_mix"], cell["cfg"], 1, 4)
    state, p0 = program.start(4)
    _, records, _ = program.first_steps(state, p0, traffic.feed(pool), bench.CHECKED_STEPS)
    state, _ = program.start(4)
    _, metrics = program.step_fn(state, pool[0], program.lr, program.damping, **program.flags_for(0))
    scalars = {k for k, v in metrics.items() if v.ndim == 0}
    assert {"loss", "accuracy"} <= scalars
    for record in records:
        assert set(record["counters"]) == scalars and record["counters"]["loss"] == record["loss"]
        assert all(isinstance(v, float) for v in record["counters"].values())
    assert records[0]["counters"]["loss"] == pytest.approx(float(metrics["loss"]), rel=1e-6)


def unchanged_state(step):
    def broken(state, *args, **flags):
        _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), *args, **flags)
        return state, metrics
    return broken


def half_batch(step):
    def broken(state, batch, *args, **flags):
        batch = jax.tree_util.tree_map(lambda a: jnp.concatenate([a[: len(a) // 2]] * 2), batch)
        return step(state, batch, *args, **flags)
    return broken


CONV = "rn50_px32_b4"  # the conv cell's runs take minutes each on the CPU: -k "not conv" leaves them out


def test_conv_rehearsal_is_correct():
    result = run_tiny(CONV, seed=2**31 + 12)
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    assert result["info"]["steps_by_kind"].keys() == {"plain", "factors", "refresh"}
    assert {"grad1_median_leaf", "grad1_worst_leaf", "delta3_median_leaf"} <= result["checks"].keys()


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
@pytest.mark.parametrize("name", ["lm_tiny_t32", CONV], ids=["lm", "conv"])
def test_planted_fault_is_not_correct(name, fault):
    result = run_tiny(name, break_step=fault)
    assert result["correct"] is False
    over = [n for n, row in result["checks"].items() if row["value"] > row["limit"]]
    assert over, result["checks"]
    if fault is unchanged_state:
        # a state left unchanged reads 1 by the leaf measures
        moved = "delta3_worst_leaf" if name == "lm_tiny_t32" else "delta3_median_leaf"
        assert result["checks"][moved]["value"] == pytest.approx(1.0, abs=0.01)


def test_control_in_bfloat16_is_not_correct():
    # the control: the reference in the nearest precision below, in the program's place
    cell = tiny_cell("lm_tiny_t32")
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


def test_conv_control_the_trainers_own_bf16_is_not_correct():
    # the conv cells' control: the program's own lower-precision path (--bf16) in the program's place
    cell = tiny_cell(CONV)
    check = bench.load_module(bench.HERE, "check.py")
    traffic = bench.load_module(bench.HERE, "traffic.py")
    program = bench.Program(cell, jax.devices()[:1], lower_precision=True)
    state, p0 = program.start(9)
    pool = traffic.make_pool(cell["traffic_mix"], cell["cfg"], 1, 9)
    _, _, control = program.first_steps(state, p0, traffic.feed(pool), bench.CHECKED_STEPS)
    values, _ = check.readings(control, bench.run_reference(cell, p0, pool, float(program.lr)))
    correct, rows = check.decide(values, {k: v for k, v in cell["file"]["limits"].items() if k in values})
    assert correct is False, rows
    assert rows["grad1_median_leaf"]["value"] > rows["grad1_median_leaf"]["limit"]


def test_no_chip_is_a_nonzero_exit():
    with pytest.raises(SystemExit) as e:
        bench.find_devices(1)
    assert e.value.code not in (0, None)
    assert "TPU" in str(e.value.code)
