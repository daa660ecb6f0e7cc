"""Block-scaled int8 factor wire (``KFAC(factor_comm_dtype="int8")``).

Pins the sub-bf16 wire's four contracts on the 8-device CPU mesh:

* **quantizer math** — block-scaled stochastic rounding is unbiased, exact
  on all-zero blocks, bounded by one scale step per element, and the
  error-feedback recursion keeps the carried residual bounded while the
  TIME-AVERAGED dequantized stream converges to the true payload (the
  property that lets an EMA survive an 8-bit wire);
* **training parity** — a deferred int8 run tracks the f32 wire at
  quantization-noise level across ≥ 2 eigen-refresh intervals, with the
  residual state actually engaged (non-zero, per-replica divergent);
* **exact byte accounting** — measured ``last_wire_bytes`` equals
  ``quant_wire_bytes`` (1 byte/element + 4 per 256-block scale ≈ 0.51×
  the bf16 wire), and the planner's ``plan_wire_bytes`` predicts the same
  number the comm plane measures;
* **state durability + refusals** — ``wire_error`` survives the elastic
  snapshot round-trip bitwise through the replica-local packing, the
  manifest names it, and the unsound compositions refuse at construction
  (per-step exchange without a residual slot; owner sharding's
  psum_scatter wire) while pallas×inverse degrades with a warning.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_pytorch_tpu import KFAC, EigenRefreshCadence
from kfac_pytorch_tpu.compile_cache import expected_step_variants
from kfac_pytorch_tpu.elastic import Supervisor, state_io
from kfac_pytorch_tpu.parallel import comm
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh
from kfac_pytorch_tpu.planner import Plan, model_facts, plan_wire_bytes
from kfac_pytorch_tpu.training.step import kfac_flags_for_step
from tests.test_factor_sharding import _MLP, _put, _setup


# ------------------------------------------------------------- quantizer


def test_quantize_roundtrip_bounds_and_zero_block():
    r = np.random.RandomState(0)
    # ragged length: exercises the block padding; scale spread across
    # blocks exercises the per-block amax
    buf = jnp.asarray(
        np.concatenate([r.randn(300) * 1e3, r.randn(217) * 1e-3]).astype(
            np.float32
        )
    )
    codes, scale = comm.quantize_bucket(buf, jax.random.PRNGKey(1))
    assert codes.dtype == jnp.int8 and codes.shape == (3, 256)
    deq = comm.dequantize_bucket(codes, scale, int(buf.shape[0]))
    err = np.abs(np.asarray(deq - buf))
    per_elem_bound = np.repeat(np.asarray(scale)[:, 0], 256)[: buf.shape[0]]
    assert np.all(err <= per_elem_bound + 1e-12)
    # the all-quiet third block (elements 512+) gets its OWN small scale —
    # a single per-bucket amax would round its values with ~1e1 steps
    assert np.max(err[512:]) < 1e-4

    z_codes, z_scale = comm.quantize_bucket(
        jnp.zeros((256,), jnp.float32), jax.random.PRNGKey(2)
    )
    assert np.all(np.asarray(z_codes) == 0)
    np.testing.assert_array_equal(np.asarray(z_scale), 1.0)


def test_quantization_is_unbiased():
    r = np.random.RandomState(3)
    buf = jnp.asarray(r.randn(256).astype(np.float32))
    acc = np.zeros(256, np.float64)
    trials = 200
    for t in range(trials):
        codes, scale = comm.quantize_bucket(buf, jax.random.PRNGKey(t))
        acc += np.asarray(comm.dequantize_bucket(codes, scale, 256))
    scale_step = float(np.max(np.abs(np.asarray(buf)))) / 127.0
    # E[dequant] = x: the mean over keys lands well inside one scale step
    assert np.max(np.abs(acc / trials - np.asarray(buf))) < scale_step / 2


def test_error_feedback_residual_bounded_and_mean_converges():
    """The deferred-flush recursion: e ← (x + e) − dq(x + e). The residual
    never grows past one scale step per element, and the running mean of
    what went on the wire converges to x — the carried error decays out of
    the time average instead of biasing the EMA."""
    r = np.random.RandomState(4)
    x = np.asarray(r.randn(256).astype(np.float32))
    scale_step = float(np.max(np.abs(x))) / 127.0
    e = np.zeros_like(x)
    wire_mean = np.zeros_like(x, dtype=np.float64)
    errs = []
    for t in range(32):
        payload = jnp.asarray(x + e)
        codes, scale = comm.quantize_bucket(payload, jax.random.PRNGKey(t))
        deq = np.asarray(
            comm.dequantize_bucket(codes, scale, 256), np.float64
        )
        e = np.asarray(payload, np.float64) - deq
        assert np.max(np.abs(e)) <= 2 * scale_step  # bounded, not drifting
        wire_mean += deq
        errs.append(np.max(np.abs(wire_mean / (t + 1) - x)))
    assert errs[-1] < errs[0] / 4  # the time-average error decays
    assert errs[-1] < scale_step


def test_quant_wire_bytes_is_half_bf16():
    sizes = [100_000, 777]
    got = comm.quant_wire_bytes(sizes)
    want = sum(s + -(-s // 256) * 4 for s in sizes)
    assert got == want
    bf16 = sum(sizes) * 2
    assert got < 0.52 * bf16  # codes + 1.6% scale overhead ≈ 0.51×


# -------------------------------------------- deferred training parity


def _run(kw_extra, steps=7, seed=0):
    mesh = data_parallel_mesh()
    kw = dict(damping=0.01, fac_update_freq=1, kfac_update_freq=3,
              factor_comm_freq=2, mesh=mesh)
    kw.update(kw_extra)
    kfac = KFAC(**kw)
    state, fn, batch = _setup(_MLP(), kfac, mesh, seed=seed)
    state, b = _put(state, batch, mesh, kfac)
    for step in range(steps):
        fl = kfac_flags_for_step(step, kfac)
        state, _ = fn(state, b, jnp.float32(0.05), jnp.float32(0.01), **fl)
    return state, kfac


def test_int8_deferred_run_tracks_f32_wire():
    """7 steps at kfac_update_freq=3 = two refresh intervals, each reading
    quantized-merged factors; parity holds at quantization-noise level and
    the residual accumulators are live and replica-divergent."""
    s_f32, _ = _run({})
    s_int8, kfac = _run({"factor_comm_dtype": "int8"})
    diffs = [
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32))))
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(s_f32.params)),
            jax.tree_util.tree_leaves(jax.device_get(s_int8.params)),
        )
    ]
    assert max(diffs) < 2e-2   # tracks the f32 wire
    assert max(diffs) > 0.0    # ...and the quantizer actually engaged

    wire_error = s_int8.kfac_state["wire_error"]
    assert set(wire_error) == {
        f"b{i}" for i in range(len(wire_error))
    }
    norms = [
        float(jnp.linalg.norm(v.astype(jnp.float32)))
        for v in wire_error.values()
    ]
    assert any(n > 0 for n in norms)
    # per-replica divergence: each replica carries ITS payload's residual
    shards = [
        np.asarray(s.data)
        for s in list(wire_error.values())[0].addressable_shards
    ]
    assert any(not np.array_equal(shards[0], s) for s in shards[1:])


def test_measured_bytes_match_quant_accounting_and_planner():
    s_bf16, k_bf16 = _run({"factor_comm_dtype": "bf16"}, steps=4)
    s_int8, k_int8 = _run({"factor_comm_dtype": "int8"}, steps=4)
    bf16_bytes = k_bf16.factor_comm.last_wire_bytes
    int8_bytes = k_int8.factor_comm.last_wire_bytes
    assert bf16_bytes and int8_bytes
    sizes = [b.size for b in k_int8.factor_comm._plans[
        next(iter(k_int8.factor_comm._plans))
    ]]
    assert int8_bytes == comm.quant_wire_bytes(sizes)
    assert 0.45 * bf16_bytes < int8_bytes < 0.55 * bf16_bytes

    # the cost model predicts the SAME numbers the comm plane measured on
    # the SAME live model — plan_drift_wire_bytes = 1.0 is this equality
    facts = model_facts(jax.device_get(s_int8.params))
    assert plan_wire_bytes(
        facts, Plan(factor_comm_dtype="int8", factor_comm_freq=2)
    ) == int8_bytes
    assert plan_wire_bytes(facts, Plan(factor_comm_dtype="bf16")) == (
        bf16_bytes
    )


# ------------------------------------------------- snapshot round-trip


def test_wire_error_survives_snapshot_roundtrip(tmp_path):
    mesh = data_parallel_mesh()
    kfac = KFAC(damping=0.01, fac_update_freq=1, kfac_update_freq=3,
                factor_comm_freq=2, factor_comm_dtype="int8", mesh=mesh)
    state, fn, batch = _setup(_MLP(), kfac, mesh)
    state, b = _put(state, batch, mesh, kfac)
    cad = EigenRefreshCadence(kfac)
    for i in range(4):
        fl = cad.flags_for_step(i)
        state, _ = fn(state, b, jnp.float32(0.05), jnp.float32(0.01), **fl)

    assert "wire_error" in state.kfac_state
    assert "wire_error" in state_io.KFAC_STATE_KEYS
    manifest = state_io.build_manifest(jax.device_get(state.kfac_state))
    assert "wire_error" in manifest["kfac_state_keys"]

    sup = Supervisor(str(tmp_path), kfac=kfac, cadence=cad)
    snap = sup.snapshot(4, state, sync=True)
    restored, _ = state_io.restore_snapshot(
        snap, jax.device_get(state), kfac=kfac
    )
    for a, b2 in zip(
        jax.tree_util.tree_leaves(jax.device_get(state)),
        jax.tree_util.tree_leaves(jax.device_get(restored)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b2))
    # the restored residuals keep their per-replica (divergent) values
    a0 = state.kfac_state["wire_error"]
    r0 = restored.kfac_state["wire_error"]
    for key in a0:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a0[key])),
            np.asarray(jax.device_get(r0[key])),
        )


# ------------------------------------------------- refusals / degrades


def test_int8_without_deferral_refuses():
    mesh = data_parallel_mesh()
    with pytest.raises(ValueError, match="int8_wire_requires_deferral"):
        KFAC(damping=0.01, mesh=mesh, factor_comm_dtype="int8")


def test_int8_with_owner_sharding_refuses():
    mesh = data_parallel_mesh()
    with pytest.raises(ValueError, match="int8_wire_vs_owner_sharding"):
        KFAC(damping=0.01, mesh=mesh, factor_comm_dtype="int8",
             factor_comm_freq=2, factor_sharding="owner")


def test_int8_wire_does_not_widen_variant_budget():
    """The int8 wire swaps the flush program's merge BODY — the flag
    schedule (and so the recompile-monitor budget) must not move. This is
    the pin compile_cache.expected_step_variants' docstring names."""
    mesh = data_parallel_mesh()
    kw = dict(damping=0.01, fac_update_freq=1, kfac_update_freq=3, mesh=mesh,
              factor_comm_freq=2)
    base = expected_step_variants(KFAC(**kw))
    assert expected_step_variants(KFAC(**kw, factor_comm_dtype="int8")) == base
    kfac = KFAC(**kw)
    assert expected_step_variants(
        kfac, plan=Plan(factor_comm_freq=2)
    ) == expected_step_variants(
        kfac, plan=Plan(factor_comm_freq=2, factor_comm_dtype="int8")
    )
