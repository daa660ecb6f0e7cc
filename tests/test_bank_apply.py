"""An expert bank preconditioned from the rows each expert saw
(ops/precondition.py::precondition_bank_rows): ``v_e = (X_e·iA_e)ᵀ(ΔY_e·iG_e)``
equals ``iG_e · g_e · iA_e`` over the dense ``g_e = ΔY_eᵀ X_e`` to float32
rounding, on both paths of ops/grouped.py, with empty groups and garbage past
the groups; in the tiny GLM model a step, captured or plain, takes the same
parameters either way, under any gradient clip; the shape rule and the gauge
``kfac/apply_bank_routed`` say where it engages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import test_glm_moe_lite as glm
from kfac_pytorch_tpu import KFAC, capture
from kfac_pytorch_tpu.observability.telemetry import configure, get_telemetry
from kfac_pytorch_tpu.ops import grouped
from kfac_pytorch_tpu.ops import precondition as precond_ops
from kfac_pytorch_tpu.training.step import TrainState, make_sgd, make_train_step

ROWS = 256


def _spd_inverses(rng, count, side):
    m = rng.standard_normal((count, side, side)) / np.sqrt(side)
    return jnp.asarray(np.linalg.inv(m @ m.transpose(0, 2, 1) + 0.1 * np.eye(side)), jnp.float32)


def _tape(rng, sizes, a, m, garbage):
    x = rng.standard_normal((ROWS, a)).astype(np.float32)
    dy = rng.standard_normal((ROWS, m)).astype(np.float32)
    if garbage:  # what a grouped kernel may leave past the groups
        x[sum(sizes):], dy[sum(sizes):] = np.nan, np.inf
    return jnp.asarray(x), jnp.asarray(dy), jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("garbage", [False, True], ids=["zeros_past", "nan_past"])
@pytest.mark.parametrize("sizes", [(70, 0, 100), (0, 0, 0), (ROWS, 0, 0), (40, 90, 126)],
                         ids=["one_empty", "all_empty", "one_full", "all_rows"])
@pytest.mark.parametrize("a,m", [(24, 40), (40, 24), (32, 32)])
@pytest.mark.parametrize("kernels", [False, True], ids=["ragged_dot", "pallas"])
def test_routed_form_equals_the_dense_form(kernels, a, m, sizes, garbage, monkeypatch):
    """Against ``precondition_mat_inv`` over ``g_e = ΔY_eᵀ X_e`` summed by hand,
    the inverses read in place from tables where the bank is not first."""
    if kernels:
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert grouped._use_kernels(ROWS) is kernels
    rng = np.random.default_rng(a * 1000 + m)
    rows, dy, gs = _tape(rng, sizes, a, m, garbage)
    tables = {str(a): _spd_inverses(rng, 5, a)}
    tables[str(m)] = tables[str(a)] if a == m else _spd_inverses(rng, 4, m)
    layout = {"iA": (a, 1, 3), "iG": (m, 0 if a == m else 1, 3)}
    got = precond_ops.precondition_bank_rows(rows, dy, gs, tables, layout, lax.Precision.HIGHEST)
    assert got.shape == (3, a, m)
    lo = np.concatenate([[0], np.cumsum(sizes)])
    g = jnp.stack([dy[lo[e]:lo[e + 1]].T @ rows[lo[e]:lo[e + 1]] for e in range(3)])  # [E, m, a]
    i_a = tables[str(a)][layout["iA"][1]:layout["iA"][1] + 3]
    i_g = tables[str(m)][layout["iG"][1]:layout["iG"][1] + 3]
    want = precond_ops.precondition_mat_inv(g, i_a, i_g, lax.Precision.HIGHEST).transpose(0, 2, 1)
    scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    for e, n in enumerate(sizes):
        if n == 0:
            assert bool(jnp.all(got[e] == 0))


@pytest.mark.parametrize("rows,experts,a,m,pays", [
    (8192, 8, 2048, 1536, True),   # GLM-4.7-Flash's gate / up banks at T·k rows
    (9299, 8, 2048, 1536, True),   # the bound, rounded down
    (9300, 8, 2048, 1536, False),
    (128, 4, 64, 48, True),        # tests/test_glm_moe_lite.py's banks
    (128, 4, 48, 64, True),
    (128, 1, 64, 48, False),       # one expert held: the dense form is cheaper
    (36, 1, 64, 48, True),
    (37, 1, 64, 48, False),
])
def test_the_shape_rule(rows, experts, a, m, pays):
    assert precond_ops.bank_rows_pay(rows, experts, a, m) is pays


def _gauge(trace):
    """`kfac/apply_bank_routed` after `trace()` ran with telemetry on."""
    tel = get_telemetry()
    was = tel.enabled
    try:
        configure(enabled=True)
        trace()
        return tel.gauges.get("kfac/apply_bank_routed")
    finally:
        configure(enabled=was)
        tel.reset()


def _glm_step(cfg=glm.CFG, accum_steps=1):
    model = glm.glm_moe_lite.get_model(remat=True, **glm.sizes_of(cfg))
    toks = jnp.zeros((glm.BATCH, glm.SEQ), jnp.int32)
    layers = capture.discover_layers(model, toks, train=True)
    kfac = KFAC(layers=layers, shared_a=glm.glm_moe_lite.shared_inputs(layers), damping=0.003,
                precond_method="inverse")
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), toks, train=True))["params"]
    tx = make_sgd(0.9)
    state = jax.eval_shape(lambda: TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={}, opt_state=tx.init(params),
        kfac_state=kfac.init(params)))
    step = make_train_step(model, tx, kfac, train_kwargs={"train": True}, grad_clip=0.25,
                           accum_steps=accum_steps)
    batch = glm.batches(1)[0]
    if accum_steps > 1:
        batch = jax.tree_util.tree_map(lambda x: x.reshape(accum_steps, -1, *x.shape[1:]), batch)
    return kfac, step, state, batch


FLAGS = {
    "plain": dict(update_factors=False, update_eigen=False),
    "factors": dict(update_factors=True, update_eigen=False),
    "refresh": dict(update_factors=True, update_eigen=True),
}


@pytest.mark.parametrize("kind", list(FLAGS))
@pytest.mark.parametrize("accum_steps", [1, 2], ids=["whole_batch", "two_microbatches"])
def test_gauge_counts_the_banks_of_every_whole_batch_program(kind, accum_steps):
    """Six K-FAC banks in the tiny model (gate, up, down of two expert
    layers): all six by their rows in every program that takes the batch
    whole, the plain one included (it keeps the banks' tape too), none under
    accumulation (one microbatch's rows do not make the summed gradient),
    whatever program was traced before."""
    kfac, step, state, batch = _glm_step(accum_steps=accum_steps)
    assert sum(capture.split_bank_name(n)[1] is not None for n in kfac.layers) == 6

    def trace():
        other = FLAGS["plain" if kind != "plain" else "factors"]
        for flags in (other, FLAGS[kind]):
            step.trace(state, batch, jnp.float32(0.01), jnp.float32(0.003), **flags)

    assert _gauge(trace) == (6 if accum_steps == 1 else 0)


def test_a_plain_step_perturbs_the_banks_alone():
    """The plain program's perturbations: the six K-FAC banks' outputs and no
    other layer's (capture.bank_perturbation_zeros)."""
    kfac, _, _, batch = _glm_step()
    model = glm.glm_moe_lite.get_model(remat=True, **glm.sizes_of(glm.CFG))
    perts = capture.bank_perturbation_zeros(model, kfac.layers, batch[0], train=True)
    banks = [capture.split_bank_name(n)[0] for n in kfac.layers if capture.split_bank_name(n)[1]]
    paths = ["/".join(k.key for k in path[:-1]) for path, _ in jax.tree_util.tree_flatten_with_path(perts)[0]]
    assert sorted(paths) == sorted(banks) and len(banks) == 6


def test_the_plain_program_carries_no_capture_phase():
    """The tape costs the plain program no op under ``kfac_capture``: the
    benchmark's trace reader (benchmarks/trace_phases.py) refuses a plain run
    that shows one. The routed products sit under ``kfac_apply``."""
    import test_phases

    _, step, state, batch = _glm_step()
    text = step.lower(state, batch, jnp.float32(0.01), jnp.float32(0.003),
                      **FLAGS["plain"]).as_text(debug_info=True)
    found = {test_phases.phase_of(n) for n in test_phases._op_names(text)}
    assert "kfac_apply" in found
    assert not found & {"kfac_capture", "kfac_refresh"}


def test_rows_past_the_bound_keep_the_dense_form():
    """One expert held of sixteen, top-2: 128 rows against a bound of 36."""
    cfg = glm.cfg_with(held_experts=[0, 1])
    _, step, state, batch = _glm_step(cfg)
    trace = lambda: step.trace(state, batch, jnp.float32(0.01), jnp.float32(0.003), **FLAGS["factors"])
    assert _gauge(trace) == 0


@pytest.mark.parametrize("kind", list(FLAGS))
def test_dense_models_never_reach_the_tables(kind, monkeypatch):
    """The tiny transformer LM (GPT-2's block): no bank, no tape, no table."""
    import test_cross_entropy

    def refuse(*args, **kwargs):
        raise AssertionError("a dense model reached the inverse tables")

    monkeypatch.setattr(precond_ops, "precondition_all_inv_tables", refuse)
    monkeypatch.setattr(precond_ops, "precondition_bank_rows", refuse)
    monkeypatch.setattr(capture, "bank_perturbation_zeros", refuse)
    _, state, batch, step = test_cross_entropy._tiny_lm_step()
    shapes = jax.eval_shape(lambda: state)
    trace = lambda: step.trace(shapes, batch, jnp.float32(0.01), jnp.float32(0.01), **FLAGS[kind])
    assert _gauge(trace) == 0


@pytest.mark.parametrize("grad_clip", [0.0, 0.25, 0.02], ids=["no_clip", "config_clip", "hard_clip"])
def test_a_step_takes_the_same_parameters_either_way(grad_clip, monkeypatch):
    """Steps 0, 1 and 2 (refresh, factors and a plain step that captures
    nothing) of the tiny GLM model from the same state, by rows and densely:
    the same parameters, momentum and factors to float32 rounding. The clip
    factor reaches the routed form (the gradient norm is about 1.7: 0.25 and
    0.02 scale it by about 0.15 and 0.012; 0 leaves it unscaled)."""
    cfg = dict(glm.CFG, grad_clip=grad_clip)
    data = glm.batches(3)

    def run():
        _, kfac, step, state, p0 = glm.program(cfg)
        for k in range(3):
            flags = glm.kfac_flags_for_step(k, kfac)
            if k == 2:
                flags = dict(flags, **FLAGS["plain"])
            state, _ = step(state, data[k], jnp.float32(cfg["base_lr"]), jnp.float32(0.003), **flags)
        return state, p0

    routed, p0 = run()
    monkeypatch.setattr(precond_ops, "bank_rows_pay", lambda *a: False)
    dense, _ = run()
    moved = lambda s: jax.tree_util.tree_map(lambda a, b: a - b, s.params, p0)
    assert glm.worst_leaf(moved(routed), moved(dense)) < 1e-4
    trace = lambda s: next(t.trace for t in s.opt_state if hasattr(t, "trace"))
    assert glm.worst_leaf(trace(routed), trace(dense)) < 1e-4
    assert glm.worst_leaf(routed.kfac_state["factors"], dense.kfac_state["factors"]) < 1e-6


@pytest.mark.parametrize("cell,benchmark,want", [
    ("glm_tiny_t64", "benchmark_tiny_glm.json", {"plain": 6, "factors": 6, "refresh": 6, "twin": 0}),
    ("lm_tiny_t32", "benchmark_tiny.json", {"plain": 0, "factors": 0, "refresh": 0}),
])
def test_the_gauge_script_reads_each_program_of_a_cell(cell, benchmark, want, capsys):
    """scripts/program_gauges.py, which reads the gauges of a cell's programs
    at full size on a TPU, at the benchmark's tiny cells:
    ``kfac/apply_bank_routed`` and ``kfac/refresh_sweep_pivots``."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location("program_gauges", os.path.join(root, "scripts", "program_gauges.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    tel = get_telemetry()
    was = tel.enabled
    try:
        script.main(cell, ",".join(want), os.path.join(root, "benchmarks", "tests", benchmark))
    finally:
        configure(enabled=was)
        tel.reset()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert {l["program"]: l["gauges"]["kfac/apply_bank_routed"] for l in lines} == want
    assert all(l["gauges"]["loss/closed_form_calls"] == 1 for l in lines)
    # the refresh's serial pivot steps: one a table (sides up to 64) or a stack
    # (the tiny LM's six sides), in the refresh program alone
    pivots = {"refresh": 4 if cell == "glm_tiny_t64" else 6}
    assert {l["program"]: l["gauges"]["kfac/refresh_sweep_pivots"] for l in lines} == {
        p: pivots.get(p, 0) for p in want}


def test_a_data_parallel_step_takes_the_same_parameters_either_way(monkeypatch):
    """The batch split over two devices under GSPMD: the tape is the global
    rows, the grouped products take the `ragged_dot` path, and steps 0 and 1
    give the dense form's parameters."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh(jax.devices()[:2])
    replicated, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    model = glm.glm_moe_lite.get_model(remat=True, **glm.sizes_of(glm.CFG))
    toks = jnp.zeros((glm.BATCH, glm.SEQ), jnp.int32)
    layers = capture.discover_layers(model, toks, train=True)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), toks, train=True))["params"]
    p0 = jax.tree_util.tree_map(np.asarray, glm.weights.make_weights(shapes, glm.weights.seed_scalar(7), glm.CFG["weights"]))
    data = [jax.device_put(b, split) for b in glm.batches(2)]

    def run():
        kfac = KFAC(layers=layers, shared_a=glm.glm_moe_lite.shared_inputs(layers), damping=0.003,
                    precond_method="inverse", mesh=mesh)
        tx = make_sgd(0.9)
        params = jax.device_put(jax.tree_util.tree_map(np.array, p0), replicated)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
                           opt_state=tx.init(params), kfac_state=jax.device_put(kfac.init(params), replicated))
        step = make_train_step(model, tx, kfac, train_kwargs={"train": True}, grad_clip=0.25)
        for k, batch in enumerate(data):
            state, _ = step(state, batch, jnp.float32(0.01), jnp.float32(0.003), **glm.kfac_flags_for_step(k, kfac))
        return jax.tree_util.tree_map(lambda a, b: a - b, state.params, p0)

    routed = run()
    monkeypatch.setattr(precond_ops, "bank_rows_pay", lambda *a: False)
    assert glm.worst_leaf(routed, run()) < 1e-4
