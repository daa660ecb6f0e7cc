"""Inverse-method preconditioning (precond_method="inverse").

Validates the π-corrected factored-Tikhonov inverses against explicit numpy
linear algebra, the refresh's blocked sweep against float64 inverses and the
Cholesky path it replaced, the 2-matmul solve against per-layer math (stacked and
unstacked layouts), the end-to-end KFAC.update pipeline against a numpy
replay, and distributed == replicated on the 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_pytorch_tpu import KFAC
from kfac_pytorch_tpu.observability.telemetry import configure, get_telemetry
from kfac_pytorch_tpu.ops import precondition as P
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh


def _rand_factors(rng, sides):
    """SPD factors per layer: {'A': [a,a], 'G': [g,g]}."""
    facs = {}
    for i, (a, g) in enumerate(sides):
        ma = rng.randn(a, a).astype(np.float32)
        mg = rng.randn(g, g).astype(np.float32)
        facs[f"l{i}"] = {
            "A": jnp.asarray(ma @ ma.T / a + np.eye(a, dtype=np.float32)),
            "G": jnp.asarray(mg @ mg.T / g + np.eye(g, dtype=np.float32)),
        }
    return facs


def _np_factored_inverse(facs, damping, eps=1e-10):
    out = {}
    for n, f in facs.items():
        A = np.asarray(f["A"], np.float64)
        G = np.asarray(f["G"], np.float64)
        pi = np.sqrt(
            max(np.trace(A) / A.shape[0], eps) / max(np.trace(G) / G.shape[0], eps)
        )
        sl = np.sqrt(damping)
        out[n] = {
            "iA": np.linalg.inv(A + pi * sl * np.eye(A.shape[0])),
            "iG": np.linalg.inv(G + (sl / pi) * np.eye(G.shape[0])),
        }
    return out


def test_factored_inverse_matches_numpy():
    rng = np.random.RandomState(0)
    facs = _rand_factors(rng, [(5, 4), (5, 4), (7, 3)])
    inv = P.factored_inverse_all(facs, jnp.float32(0.01))
    ref = _np_factored_inverse(facs, 0.01)
    for n in facs:
        np.testing.assert_allclose(np.asarray(inv[n]["iA"]), ref[n]["iA"],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(inv[n]["iG"]), ref[n]["iG"],
                                   rtol=1e-4, atol=1e-5)


def _damped_factors(k, n, cond, seed):
    """``k`` Gram factors of rank n/2 scaled to a largest eigenvalue of 1 and
    damped by 1/cond: SPD with a condition number of about ``cond``."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((k, n, n // 2))
    g = x @ x.transpose(0, 2, 1)
    v = np.ones((k, n, 1))
    for _ in range(30):  # the largest eigenvalue, by power iteration
        v = g @ v
        v /= np.linalg.norm(v, axis=1, keepdims=True)
    g /= (v.transpose(0, 2, 1) @ g @ v)
    return g + np.eye(n) / cond


def _cholesky_path(stack):
    """The refresh's inverse before the sweep: a Cholesky of side n and two
    triangular solves against the identity, at float32, symmetrised."""
    with jax.default_matmul_precision("float32"):
        inv = P._cholesky_inverse(stack)
    return 0.5 * (inv + jnp.swapaxes(inv, -1, -2))


@pytest.mark.parametrize("cond", [1e2, 1e4, 1e6])
@pytest.mark.parametrize("side", [64, 256, 257, 576, 767, 769, 1025])
def test_the_sweep_inverts_as_the_cholesky_path_does(side, cond):
    """One pivot (64, 256), a ragged last pivot of 1 (257, 769, 1025), 64
    (576) or 255 (767) after 256-wide ones: against a float64 inverse of the same float32
    input, a relative error and a residual ‖M·X − I‖ within 4x the Cholesky
    path's and the error within 4x its bound κ·u; symmetric."""
    m = jnp.asarray(_damped_factors(2, side, cond, side), jnp.float32)
    exact = np.asarray(m, np.float64)
    got = np.asarray(jax.jit(P._spd_inverse_stack)(m), np.float64)
    chol = np.asarray(jax.jit(_cholesky_path)(m), np.float64)
    want = np.linalg.inv(exact)
    residual = lambda x: np.linalg.norm(exact @ x - np.eye(side), axis=(1, 2)).max()
    error = lambda x: (np.linalg.norm(x - want, axis=(1, 2)) / np.linalg.norm(want, axis=(1, 2))).max()
    assert residual(got) <= 4 * residual(chol), (residual(got), residual(chol))
    assert error(got) <= 4 * error(chol), (error(got), error(chol))
    assert error(got) < 4 * cond * 2.0 ** -24, error(got)
    np.testing.assert_array_equal(got, got.transpose(0, 2, 1))


def _pivots_traced(fn, *args):
    """``kfac/refresh_sweep_pivots`` after tracing ``fn`` (no compile, no run)."""
    tel = get_telemetry()
    was = tel.enabled
    try:
        configure(enabled=True)
        P.reset_tallies()
        jax.eval_shape(fn, *args)
        return tel.gauges.get("kfac/refresh_sweep_pivots")
    finally:
        configure(enabled=was)
        tel.reset()


def test_the_pivot_gauge_counts_gpt2_small_refresh():
    """GPT-2 small's five stacks, sides 769 x36, 3073 x12, 2304 x12, 768 x24,
    3072 x12: 4 + 13 + 9 + 3 + 12 serial pivot steps of 256."""
    f = lambda a, g: {"A": jax.ShapeDtypeStruct((a, a), jnp.float32),
                      "G": jax.ShapeDtypeStruct((g, g), jnp.float32)}
    facs = {}
    for i in range(12):
        facs.update({f"h{i}/qkv": f(769, 2304), f"h{i}/proj": f(769, 768),
                     f"h{i}/fc": f(769, 3072), f"h{i}/fc2": f(3073, 768)})
    damping = jax.ShapeDtypeStruct((), jnp.float32)
    assert _pivots_traced(P.factored_inverse_all, facs, damping) == 41


@pytest.mark.parametrize("per", [1, 2, 5])
def test_tables_past_one_pivot_equal_one_stack(per, monkeypatch):
    """The tables' batched refresh at sides the sweep takes in two pivots (300
    = 256 + 44) equals the inverses of one stack a side; the gauge counts each
    batch's pivots."""
    facs = {f"l{i}": {"A": jnp.asarray(_damped_factors(1, 300, 1e3, 10 * per + i)[0], jnp.float32),
                      "G": jnp.asarray(_damped_factors(1, 40, 1e3, 20 * per + i)[0], jnp.float32)}
            for i in range(5)}
    whole = P.factored_inverse_all(facs, jnp.float32(0.003))
    monkeypatch.setattr(P, "TABLE_BATCH_BYTES", per * 4 * 300 * 300)
    shapes = {n: {k: tuple(v.shape) for k, v in f.items()} for n, f in facs.items()}
    layout, rows = P.inverse_table_layout(shapes, {})
    tables = {str(side): jnp.broadcast_to(jnp.eye(side), (k, side, side)) for side, k in rows.items()}
    refresh = lambda t: P.factored_inverse_tables(facs, t, jnp.float32(0.003), 1e-10, {}, layout)
    batches = -(-5 // per)
    assert _pivots_traced(refresh, tables) == 2 * batches + 1
    tables = refresh(tables)
    for name, where in layout.items():
        for key, (side, first, _) in where.items():
            got, want = tables[str(side)][first], whole[name][key]
            assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5, (name, key)


def test_precondition_all_inv_stacked_matches_unstacked():
    rng = np.random.RandomState(1)
    facs = _rand_factors(rng, [(5, 4), (5, 4), (5, 4), (6, 2)])
    inv = P.factored_inverse_all(facs, jnp.float32(0.02))
    gmats = {
        n: jnp.asarray(
            rng.randn(f["G"].shape[0], f["A"].shape[0]).astype(np.float32)
        )
        for n, f in facs.items()
    }
    plain = P.precondition_all_inv(gmats, inv)
    singles, stacked = P.split_inv_state(inv)
    assert stacked, "must exercise a stacked group"
    via_stack = P.precondition_all_inv(gmats, singles, stacked=stacked)
    for n in gmats:
        ref = np.asarray(inv[n]["iG"]) @ np.asarray(gmats[n]) @ np.asarray(inv[n]["iA"])
        np.testing.assert_allclose(np.asarray(plain[n]), ref, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(via_stack[n]), np.asarray(plain[n]), atol=1e-6
        )


def _dense_params(rng, sizes):
    params = {}
    for i, (nin, nout) in enumerate(zip(sizes[:-1], sizes[1:])):
        params[f"l{i}"] = {
            "kernel": jnp.asarray(rng.randn(nin, nout).astype(np.float32)),
            "bias": jnp.asarray(rng.randn(nout).astype(np.float32)),
        }
    return params


def _stats_for(params, rng, batch=8):
    from kfac_pytorch_tpu.ops import factors as F

    a_contribs, g_stats, grads = {}, {}, {}
    for name, layer in params.items():
        nin, nout = layer["kernel"].shape
        acts = jnp.asarray(rng.randn(batch, nin).astype(np.float32))
        gout = jnp.asarray(rng.randn(batch, nout).astype(np.float32) / batch)
        a_contribs[name] = F.compute_a_dense(acts, has_bias=True)
        g_stats[name] = F.compute_g_dense(gout, batch_averaged=True)
        grads[name] = {
            "kernel": jnp.asarray(rng.randn(nin, nout).astype(np.float32)),
            "bias": jnp.asarray(rng.randn(nout).astype(np.float32)),
        }
    return a_contribs, g_stats, grads


def test_kfac_inverse_end_to_end_matches_numpy():
    """KFAC(precond_method='inverse').update == numpy replay of
    EMA → π-damped inverses → iG·g·iA → KL clip → write-back."""
    rng = np.random.RandomState(2)
    params = _dense_params(rng, [6, 5, 4])
    a_c, g_s, grads = _stats_for(params, rng)
    lr, damping, decay, kl_clip = 0.1, 0.01, 0.95, 0.001

    kfac = KFAC(damping=damping, kl_clip=kl_clip, factor_decay=decay,
                precond_method="inverse")
    state = kfac.init(params)
    new_grads, state = kfac.update(
        grads, state, a_contribs=a_c, g_factor_stats=g_s,
        lr=lr, damping=damping, update_factors=True, update_eigen=True)

    # numpy replay
    names = list(params)
    A = {n: decay * np.eye(a_c[n].shape[0]) + (1 - decay) * np.asarray(a_c[n], np.float64)
         for n in names}
    G = {n: decay * np.eye(g_s[n].shape[0]) + (1 - decay) * np.asarray(g_s[n], np.float64)
         for n in names}
    inv = _np_factored_inverse({n: {"A": A[n], "G": G[n]} for n in names}, damping)
    vg_sum, v = 0.0, {}
    for n in names:
        gmat = np.concatenate(
            [np.asarray(grads[n]["kernel"], np.float64).T,
             np.asarray(grads[n]["bias"], np.float64)[:, None]], axis=1)
        v[n] = inv[n]["iG"] @ gmat @ inv[n]["iA"]
        vg_sum += (v[n] * gmat).sum() * lr**2
    nu = min(1.0, np.sqrt(kl_clip / abs(vg_sum)))
    for n in names:
        np.testing.assert_allclose(
            np.asarray(new_grads[n]["kernel"]), (nu * v[n][:, :-1]).T,
            rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(new_grads[n]["bias"]), nu * v[n][:, -1],
            rtol=1e-3, atol=1e-4)

    # stale-curvature step reuses the same inverses bit-for-bit
    g2, _ = kfac.update(grads, state, lr=lr, damping=damping,
                        update_factors=False, update_eigen=False)
    np.testing.assert_allclose(np.asarray(new_grads["l0"]["kernel"]),
                               np.asarray(g2["l0"]["kernel"]), atol=1e-6)


def test_kfac_inverse_distributed_matches_replicated():
    rng = np.random.RandomState(3)
    # repeated shapes -> stacked groups + singletons, like the real zoos
    params = {}
    for i, (nin, nout) in enumerate([(6, 5), (6, 5), (6, 5), (4, 3)]):
        params[f"l{i}"] = {
            "kernel": jnp.asarray(rng.randn(nin, nout).astype(np.float32)),
            "bias": jnp.asarray(rng.randn(nout).astype(np.float32)),
        }
    a_c, g_s, grads = _stats_for(params, rng)

    kfac_rep = KFAC(damping=0.01, precond_method="inverse")
    g_rep, s_rep = kfac_rep.update(
        grads, kfac_rep.init(params), a_contribs=a_c, g_factor_stats=g_s,
        lr=0.1, damping=0.01, update_factors=True, update_eigen=True)
    assert s_rep["eigen_stacked"], "must exercise stacked inverse groups"

    mesh = data_parallel_mesh()
    kfac_d = KFAC(damping=0.01, precond_method="inverse", mesh=mesh,
                  distribute_precondition=True)
    g_d, _ = kfac_d.update(
        grads, kfac_d.init(params), a_contribs=a_c, g_factor_stats=g_s,
        lr=0.1, damping=0.01, update_factors=True, update_eigen=True)
    for n in params:
        np.testing.assert_allclose(np.asarray(g_rep[n]["kernel"]),
                                   np.asarray(g_d[n]["kernel"]),
                                   rtol=1e-4, atol=1e-5)


def test_invalid_method_rejected():
    import pytest

    with pytest.raises(ValueError):
        KFAC(precond_method="cholesky")


def test_distributed_bf16_comm_close_to_replicated():
    """precond_comm_dtype=bf16 compresses the exchange; single-owner slots
    make the psum exact up to the downcast rounding (~1e-2 relative)."""
    rng = np.random.RandomState(4)
    params = _dense_params(rng, [6, 5, 4])
    a_c, g_s, grads = _stats_for(params, rng)
    kfac_rep = KFAC(damping=0.01)
    g_rep, _ = kfac_rep.update(
        grads, kfac_rep.init(params), a_contribs=a_c, g_factor_stats=g_s,
        lr=0.1, damping=0.01, update_factors=True, update_eigen=True)
    mesh = data_parallel_mesh()
    kfac_d = KFAC(damping=0.01, mesh=mesh, distribute_precondition=True,
                  precond_comm_dtype=jnp.bfloat16)
    g_d, _ = kfac_d.update(
        grads, kfac_d.init(params), a_contribs=a_c, g_factor_stats=g_s,
        lr=0.1, damping=0.01, update_factors=True, update_eigen=True)
    for n in params:
        a, b = np.asarray(g_rep[n]["kernel"]), np.asarray(g_d[n]["kernel"])
        denom = max(float(np.abs(a).max()), 1e-8)
        assert np.abs(a - b).max() / denom < 2e-2, f"{n}: bf16 comm too lossy"


def test_comm_dtype_requires_distribute():
    import pytest

    with pytest.raises(ValueError):
        KFAC(precond_comm_dtype=jnp.bfloat16)  # no distribute_precondition
