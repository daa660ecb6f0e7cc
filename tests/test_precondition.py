"""Preconditioning math: eigenbasis solve vs dense Kronecker inverse, KL clip."""

import numpy as np
import jax.numpy as jnp
import pytest

from kfac_pytorch_tpu.ops import eigh as eigh_ops
from kfac_pytorch_tpu.ops import precondition as pc


def _rand_spd(n, seed):
    rng = np.random.RandomState(seed)
    m = rng.randn(n, n).astype(np.float32)
    return m @ m.T / n + 0.1 * np.eye(n, dtype=np.float32)


def test_precondition_matches_dense_kronecker_solve():
    """v = (G ⊗ A + λI)⁻¹ vec(grad), computed densely, must match."""
    na, ng = 5, 4
    a_fac = _rand_spd(na, 0)
    g_fac = _rand_spd(ng, 1)
    rng = np.random.RandomState(2)
    grad = rng.randn(ng, na).astype(np.float32)
    damping = 0.03

    q_a, d_a = eigh_ops.eigh_with_floor(jnp.asarray(a_fac))
    q_g, d_g = eigh_ops.eigh_with_floor(jnp.asarray(g_fac))
    got = np.asarray(
        pc.precondition_mat(jnp.asarray(grad), q_a, q_g, d_a, d_g, damping)
    )

    # dense reference: note the eigenbasis solve uses dG·dAᵀ + λ (damping added
    # to the eigenvalue PRODUCT), i.e. it inverts (G ⊗ A + λ I) exactly.
    kron = np.kron(g_fac, a_fac) + damping * np.eye(na * ng, dtype=np.float32)
    want = np.linalg.solve(kron.astype(np.float64), grad.reshape(-1).astype(np.float64))
    np.testing.assert_allclose(got.reshape(-1), want, rtol=1e-3, atol=1e-4)


def test_precondition_identity_factors_is_scaled_identity():
    """With A=G=I (pre-warmup init), preconditioning is grad / (1 + damping)."""
    n = 6
    eye = jnp.eye(n)
    q, d = eigh_ops.eigh_with_floor(eye)
    rng = np.random.RandomState(3)
    grad = rng.randn(n, n).astype(np.float32)
    out = np.asarray(pc.precondition_mat(jnp.asarray(grad), q, q, d, d, 0.5))
    np.testing.assert_allclose(out, grad / 1.5, atol=1e-5)


def test_precondition_all_matches_per_layer():
    """Batched same-shape grouping must equal the per-layer reference path."""
    rng = np.random.RandomState(5)
    gmats, eigen = {}, {}
    # three layers share shape (4, 5); two others are unique
    for i, (ng, na) in enumerate([(4, 5), (4, 5), (4, 5), (3, 7), (6, 2)]):
        name = f"l{i}"
        q_a, d_a = eigh_ops.eigh_with_floor(jnp.asarray(_rand_spd(na, 10 + i)))
        q_g, d_g = eigh_ops.eigh_with_floor(jnp.asarray(_rand_spd(ng, 20 + i)))
        gmats[name] = jnp.asarray(rng.randn(ng, na).astype(np.float32))
        eigen[name] = {"QA": q_a, "dA": d_a, "QG": q_g, "dG": d_g}
    damping = jnp.float32(0.02)
    got = pc.precondition_all(gmats, eigen, damping)
    for name in gmats:
        e = eigen[name]
        want = pc.precondition_mat(
            gmats[name], e["QA"], e["QG"], e["dA"], e["dG"], damping
        )
        np.testing.assert_allclose(
            np.asarray(got[name]), np.asarray(want), rtol=1e-5, atol=1e-6
        )


@pytest.mark.parametrize(
    "k,g,a",
    [
        (1, 8, 9),        # singleton: the per-layer route
        (2, 16, 17),      # bias-augmented odd A side
        (3, 24, 25),
        (4, 10, 130),     # A side wider than one 128 lane
    ],
)
def test_precondition_all_matches_float64_formula(k, g, a):
    """A group of k same-shape layers (stacked given, stacked on the fly, or
    alone) against QG ((QGᵀ grad QA) / (dG dAᵀ + λ)) QAᵀ in float64, and the
    KL clip over the result against its own formula."""
    r = np.random.RandomState(k * 1000 + g)
    orth = lambda n: np.linalg.qr(r.randn(n, n))[0].astype(np.float32)
    gmats, eigen, want = {}, {}, {}
    for i in range(k):
        e = {"QA": orth(a), "dA": r.rand(a).astype(np.float32) + 0.1,
             "QG": orth(g), "dG": r.rand(g).astype(np.float32) + 0.1}
        grad = r.randn(g, a).astype(np.float32)
        qa, da, qg, dg, gr = (np.float64(x) for x in (*e.values(), grad))
        want[f"l{i}"] = qg @ ((qg.T @ gr @ qa) / (np.outer(dg, da) + 0.03)) @ qa.T
        gmats[f"l{i}"] = jnp.asarray(grad)
        eigen[f"l{i}"] = {n: jnp.asarray(x) for n, x in e.items()}
    _, stacked = pc.split_eigen_state(eigen)
    assert bool(stacked) == (k > 1)
    for stacks in (None, stacked):
        got = pc.precondition_all(gmats, eigen, jnp.float32(0.03), stacked=stacks)
        for name in gmats:
            np.testing.assert_allclose(
                np.asarray(got[name]), want[name], rtol=1e-4, atol=1e-5
            )
    lr, clip = 0.5, 0.001
    vg = sum(float((want[n] * np.float64(gmats[n])).sum()) for n in gmats) * lr**2
    np.testing.assert_allclose(
        float(pc.kl_clip_coefficient(got, gmats, lr, clip)),
        min(1.0, np.sqrt(clip / abs(vg))), rtol=1e-4,
    )


def test_kl_clip_no_clipping_when_small():
    ups = {"l1": jnp.full((2, 2), 1e-4)}
    grads = {"l1": jnp.full((2, 2), 1e-4)}
    nu = pc.kl_clip_coefficient(ups, grads, lr=0.1, kl_clip=0.001)
    assert float(nu) == 1.0


def test_kl_clip_matches_formula():
    rng = np.random.RandomState(4)
    v = rng.randn(3, 3).astype(np.float32)
    g = rng.randn(3, 3).astype(np.float32)
    lr, clip = 0.5, 0.001
    nu = float(pc.kl_clip_coefficient({"l": jnp.asarray(v)}, {"l": jnp.asarray(g)}, lr, clip))
    vg = float((v * g).sum() * lr**2)
    want = min(1.0, float(np.sqrt(clip / abs(vg))))
    np.testing.assert_allclose(nu, want, rtol=1e-5)


def test_kl_clip_sums_across_layers():
    v1, g1 = np.ones((2, 2), np.float32), np.ones((2, 2), np.float32)
    v2, g2 = 2 * np.ones((3,  3), np.float32), np.ones((3, 3), np.float32)
    lr, clip = 1.0, 0.001
    nu = float(
        pc.kl_clip_coefficient(
            {"a": jnp.asarray(v1), "b": jnp.asarray(v2)},
            {"a": jnp.asarray(g1), "b": jnp.asarray(g2)},
            lr,
            clip,
        )
    )
    vg = 4.0 + 18.0
    np.testing.assert_allclose(nu, np.sqrt(clip / vg), rtol=1e-5)
