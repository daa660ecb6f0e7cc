"""Fused Pallas apply path (``KFAC(apply_kernel="pallas")``).

Interpret-mode parity pins for ops/apply_kernels.py — the dense einsum
chain in ops/precondition.py is the VERBATIM oracle, so every test here
compares the kernel against the exact program the default path runs:

* the stacked precondition kernel (``fused_precondition_stack``) against
  the five-einsum rotate/scale/back-rotate chain at rtol 1e-6, across
  shape-group sizes (k = 1 singleton stacks through k = 4) plus the
  kernel's emitted ``Σ v·g`` KL-clip partials;
* the scope router (``precondition_all_with_vg``) across mixed layer
  forms — stacked dense group, singleton, diagonal-A embedding — with
  ``kl_clip_from_vg`` reproducing ``kl_clip_coefficient`` bit-for-bit on
  the same emission order;
* the fused momentum+weight-decay stream (``fused_sgd_apply``) against
  ``make_sgd``'s optax chain from an arbitrary (non-zero) trace;
* full 8-device train steps dense vs pallas(+``sgd_hyper``) composed
  with chunked refresh, deferred factor comm, and owner sharding;
* conv-form parity on a real CNN (slow marker: extra compile);
* the compile budget: ``apply_kernel`` and the int8 wire swap program
  BODIES, never flag schedules, so ``expected_step_variants`` must not
  move (the pin compile_cache.py's docstring promises lives here).

The structural side (pallas_call counts, the deleted optimizer pass, the
unchanged collective multiset) is scripts/check_apply_hlo.py's job.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu import KFAC
from kfac_pytorch_tpu.compile_cache import expected_step_variants
from kfac_pytorch_tpu.models.layers import KFACConv, KFACDense
from kfac_pytorch_tpu.ops import apply_kernels, precondition as precond_ops
from kfac_pytorch_tpu.ops.apply_kernels import (
    apply_kernel_scope,
    fused_precondition_stack,
    fused_sgd_apply,
    resolve_apply_kernel,
)
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh
from kfac_pytorch_tpu.planner import Plan
from kfac_pytorch_tpu.training.step import (
    TrainState,
    _momentum_state_index,
    kfac_flags_for_step,
    make_sgd,
    make_train_step,
)


def _orth(r, n):
    q, _ = np.linalg.qr(r.randn(n, n))
    return jnp.asarray(q, jnp.float32)


def _stack_eigen(r, k, g, a):
    """Random orthonormal bases + positive spectra for a [k, g, a] group."""
    qa = jnp.stack([_orth(r, a) for _ in range(k)])
    qg = jnp.stack([_orth(r, g) for _ in range(k)])
    da = jnp.asarray(r.rand(k, a).astype(np.float32) + 0.1)
    dg = jnp.asarray(r.rand(k, g).astype(np.float32) + 0.1)
    return qa, da, qg, dg


def _dense_oracle(gm, qa, da, qg, dg, damping):
    """The verbatim stacked chain from precondition_all (ops/precondition)."""
    v1 = jnp.einsum("kji,kjl->kil", qg, gm)
    v1 = jnp.einsum("kil,klm->kim", v1, qa)
    v2 = v1 / (dg[:, :, None] * da[:, None, :] + damping)
    v = jnp.einsum("kij,kjl->kil", qg, v2)
    return jnp.einsum("kil,kml->kim", v, qa)


# ------------------------------------------------------------ the kernel


@pytest.mark.parametrize(
    "k,g,a",
    [
        (1, 8, 9),        # singleton stack (the k=1 route)
        (2, 16, 17),      # bias-augmented odd A side
        (3, 24, 25),
        (4, 10, 130),     # A side wider than one 128 lane
    ],
)
def test_fused_precondition_stack_matches_dense_oracle(k, g, a):
    r = np.random.RandomState(k * 1000 + g)
    gm = jnp.asarray(r.randn(k, g, a).astype(np.float32))
    qa, da, qg, dg = _stack_eigen(r, k, g, a)
    damping = jnp.float32(0.03)
    want = _dense_oracle(gm, qa, da, qg, dg, damping)
    v, vg = fused_precondition_stack(
        gm, qa, da, qg, dg, damping, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(v), np.asarray(want), rtol=1e-6, atol=1e-6
    )
    # the KL-clip partials the kernel emits ARE the Σ v·g the dense path
    # re-reads from HBM
    want_vg = jnp.sum(want * gm, axis=(1, 2))
    np.testing.assert_allclose(
        np.asarray(vg), np.asarray(want_vg), rtol=1e-5, atol=1e-5
    )


def test_scope_routing_and_resolution():
    """auto resolves to dense; the scope is trace-time state; the fused SGD
    dispatcher refuses to engage under a dense scope."""
    assert resolve_apply_kernel("auto") == "dense"
    assert resolve_apply_kernel("pallas") == "pallas"
    assert resolve_apply_kernel("dense") == "dense"
    with pytest.raises(ValueError):
        resolve_apply_kernel("cuda")
    assert apply_kernels.active_apply_kernel() == "dense"
    with apply_kernel_scope("pallas"):
        assert apply_kernels.active_apply_kernel() == "pallas"
        with apply_kernel_scope("dense"):
            assert apply_kernels.active_apply_kernel() == "dense"
        assert apply_kernels.active_apply_kernel() == "pallas"
    assert apply_kernels.active_apply_kernel() == "dense"
    p = {"w": jnp.ones((3,))}
    assert (
        apply_kernels.dispatch_sgd_apply(p, p, p, jnp.float32(0.1), 0.9, 0.0)
        is None
    )


# ------------------------------------------------- the mixed-form router


def _mixed_fixture():
    """Stacked pair + singleton + diagonal-A embedding entry."""
    r = np.random.RandomState(7)
    grads, eigen = {}, {}
    for name in ("fc1", "fc2"):  # one (12, 9) shape group
        grads[name] = jnp.asarray(r.randn(12, 9).astype(np.float32))
        qa, da, qg, dg = _stack_eigen(r, 1, 12, 9)
        eigen[name] = {"QA": qa[0], "dA": da[0], "QG": qg[0], "dG": dg[0]}
    grads["head"] = jnp.asarray(r.randn(5, 13).astype(np.float32))
    qa, da, qg, dg = _stack_eigen(r, 1, 5, 13)
    eigen["head"] = {"QA": qa[0], "dA": da[0], "QG": qg[0], "dG": dg[0]}
    # embedding: G factor on features, diagonal A over the vocab axis
    grads["emb"] = jnp.asarray(r.randn(6, 11).astype(np.float32))
    _, _, qg, dg = _stack_eigen(r, 1, 6, 11)
    eigen["emb"] = {
        "QG": qg[0],
        "dG": dg[0],
        "dA": jnp.asarray(r.rand(11).astype(np.float32) + 0.1),
    }
    return grads, eigen


def test_precondition_all_with_vg_matches_dense_across_forms():
    grads, eigen = _mixed_fixture()
    damping = jnp.float32(0.02)
    lr = jnp.float32(3.0)  # large: pushes the clip coefficient below 1
    want = precond_ops.precondition_all(grads, eigen, damping)
    want_clip = precond_ops.kl_clip_coefficient(want, grads, lr, 0.001)

    out_d, vg_d = precond_ops.precondition_all_with_vg(grads, eigen, damping)
    assert vg_d is None  # dense scope: oracle delegation, no partials
    assert set(out_d) == set(want)

    with apply_kernel_scope("pallas"):
        out_p, vg_p = precond_ops.precondition_all_with_vg(
            grads, eigen, damping
        )
    assert vg_p is not None and len(vg_p) == len(grads)
    for name in want:
        np.testing.assert_allclose(
            np.asarray(out_p[name]), np.asarray(want[name]),
            rtol=1e-6, atol=1e-6,
        )
    got_clip = precond_ops.kl_clip_from_vg(vg_p, lr, 0.001)
    assert float(want_clip) < 1.0  # the clip is actually engaged
    np.testing.assert_allclose(
        float(got_clip), float(want_clip), rtol=1e-6
    )


# ---------------------------------------------------- the fused SGD pass


def test_fused_sgd_apply_matches_optax():
    """One flattened Pallas stream == add_decayed_weights ∘ trace ∘ -lr,
    from a non-zero momentum trace and over ragged leaf shapes."""
    r = np.random.RandomState(3)
    params = {
        "fc": {"kernel": jnp.asarray(r.randn(7, 5).astype(np.float32)),
               "bias": jnp.asarray(r.randn(5).astype(np.float32))},
        "conv": jnp.asarray(r.randn(2, 3, 4).astype(np.float32)),
    }
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(r.randn(*p.shape).astype(np.float32)), params
    )
    trace = jax.tree_util.tree_map(
        lambda p: jnp.asarray(r.randn(*p.shape).astype(np.float32)), params
    )
    lr, mu, wd = jnp.float32(0.07), 0.9, 5e-4

    tx = make_sgd(momentum=mu, weight_decay=wd)
    opt_state = tx.init(params)
    ti = _momentum_state_index(opt_state)
    opt_state = tuple(
        s._replace(trace=trace) if i == ti else s
        for i, s in enumerate(opt_state)
    )
    updates, new_opt = tx.update(grads, opt_state, params)
    want_p = jax.tree_util.tree_map(
        lambda p, u: p - lr * u, params, updates
    )
    want_m = new_opt[ti].trace

    got_p, got_m = fused_sgd_apply(
        params, grads, trace, lr, mu, wd, interpret=True
    )
    for a, b in zip(jax.tree_util.tree_leaves(got_p),
                    jax.tree_util.tree_leaves(want_p)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )
    for a, b in zip(jax.tree_util.tree_leaves(got_m),
                    jax.tree_util.tree_leaves(want_m)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )
    assert jax.tree_util.tree_structure(got_p) == (
        jax.tree_util.tree_structure(params)
    )


# -------------------------------------------- full train steps, composed


class _MLP(nn.Module):
    """fc1/fc2 share a factor shape → a stacked group; head is singleton."""

    @nn.compact
    def __call__(self, x, train=True):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(KFACDense(32, name="fc1")(x))
        x = nn.relu(KFACDense(32, name="fc2")(x))
        return KFACDense(10, name="fc3")(x)


class _CNN(nn.Module):
    """Conv-form coverage: KFAC conv capture feeds patch-matrix factors."""

    @nn.compact
    def __call__(self, x, train=True):
        x = nn.relu(KFACConv(8, (3, 3), name="c1")(x))
        x = nn.relu(KFACConv(8, (3, 3), name="c2")(x))
        x = x.reshape((x.shape[0], -1))
        return KFACDense(10, name="head")(x)


def _run(model, x_shape, kw_extra, *, pallas, steps=7, seed=0):
    """7 steps at kfac_update_freq=3 crosses two refresh boundaries."""
    mesh = data_parallel_mesh()
    kw = dict(damping=0.01, fac_update_freq=1, kfac_update_freq=3, mesh=mesh)
    kw.update(kw_extra)
    if pallas:
        kw["apply_kernel"] = "pallas"
    kfac = KFAC(**kw)
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(*x_shape).astype(np.float32))
    y = jnp.asarray(r.randint(0, 10, size=x_shape[0]))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    tx = make_sgd(momentum=0.9, weight_decay=5e-4)
    params = variables["params"]
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(params),
        kfac_state=kfac.init(params),
    )
    fn = make_train_step(
        model, tx, kfac, train_kwargs={"train": True}, mesh=mesh,
        grad_comm_dtype=jnp.float32,
        sgd_hyper=(0.9, 5e-4) if pallas else None,
    )
    repl = NamedSharding(mesh, P())
    if kfac.owner_sharded:
        kstate = jax.device_put(
            state.kfac_state, kfac.state_shardings(state.kfac_state)
        )
        state = state.replace(kfac_state=None)
        state = jax.device_put(state, repl)
        state = state.replace(kfac_state=kstate)
    else:
        state = jax.device_put(state, repl)
    b = tuple(
        jax.device_put(v, NamedSharding(mesh, P("data"))) for v in (x, y)
    )
    for step in range(steps):
        fl = kfac_flags_for_step(step, kfac)
        state, _ = fn(state, b, jnp.float32(0.05), jnp.float32(0.01), **fl)
    return state


def _assert_params_close(sa, sb, rtol=1e-6, atol=1e-6):
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(sa.params)),
        jax.tree_util.tree_leaves(jax.device_get(sb.params)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol
        )


@pytest.mark.parametrize(
    "extra",
    [
        pytest.param({}, id="base"),
        pytest.param({"eigh_chunks": 2}, id="eigh_chunks"),
        pytest.param({"factor_comm_freq": 2}, id="comm_freq"),
        pytest.param({"factor_sharding": "owner"}, id="owner"),
    ],
)
def test_pallas_train_step_matches_dense(extra):
    """Fused apply + fused SGD vs dense + optax, same batches, same
    schedule — composed with the chunked refresh, deferred factor comm,
    and owner-sharded layouts the apply path must coexist with."""
    s_dense = _run(_MLP(), (16, 4, 6), dict(extra), pallas=False)
    s_fused = _run(_MLP(), (16, 4, 6), dict(extra), pallas=True)
    _assert_params_close(s_dense, s_fused)


@pytest.mark.slow
def test_pallas_conv_train_step_matches_dense():
    s_dense = _run(_CNN(), (8, 8, 8, 3), {}, pallas=False, steps=5)
    s_fused = _run(_CNN(), (8, 8, 8, 3), {}, pallas=True, steps=5)
    _assert_params_close(s_dense, s_fused)


# ------------------------------------------------------ compile budgets


def test_apply_kernel_and_int8_wire_do_not_widen_variant_budget():
    """The fused apply and the int8 wire swap compiled program BODIES —
    the flag schedule (and so the recompile-monitor budget) must not move.
    This is the pin compile_cache.expected_step_variants' docstring names."""
    mesh = data_parallel_mesh()
    kw = dict(damping=0.01, fac_update_freq=1, kfac_update_freq=3, mesh=mesh,
              factor_comm_freq=2)
    base = expected_step_variants(KFAC(**kw))
    assert expected_step_variants(KFAC(**kw, apply_kernel="pallas")) == base
    assert (
        expected_step_variants(KFAC(**kw, factor_comm_dtype="int8")) == base
    )
    kfac = KFAC(**kw)
    plan = Plan(factor_comm_freq=2)
    assert expected_step_variants(kfac, plan=plan) == expected_step_variants(
        kfac, plan=Plan(factor_comm_freq=2, factor_comm_dtype="int8",
                        apply_kernel="pallas")
    )


@pytest.mark.parametrize("backend", ["tpu", "cpu", "gpu"])
def test_auto_is_dense_on_every_backend(monkeypatch, backend):
    """The fused apply kernel is opt-in: the v5e compiler refuses it for
    every multi-layer shape group (docs/PERF.md), so "auto" never picks it."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_apply_kernel("auto") == "dense"
    assert KFAC(damping=0.01).apply_kernel == "dense"


def test_default_interpret_by_backend(monkeypatch):
    assert apply_kernels._default_interpret(None) is True  # CPU tier-1
    assert apply_kernels._default_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert apply_kernels._default_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        apply_kernels._default_interpret(None)
