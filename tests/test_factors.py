"""Unit tests for factor math vs independent numpy references.

Expected values are computed with plain numpy einsum implementations of the
K-FAC factor definitions (SURVEY.md §2.1), independent of the library code.
"""

import re

import flax.linen as nn
import jax
import numpy as np
import jax.numpy as jnp
import pytest
from jax import lax

from kfac_pytorch_tpu import KFAC, capture
from kfac_pytorch_tpu.models import transformer_lm
from kfac_pytorch_tpu.models.layers import KFACDense
from kfac_pytorch_tpu.observability.telemetry import configure, get_telemetry
from kfac_pytorch_tpu.ops import factors
from kfac_pytorch_tpu.training.step import TrainState, make_sgd, make_train_step


def _np_patches(x, kh, kw, sh, sw, ph, pw):
    """Naive im2col, NHWC, channel-major (c, kh, kw) feature order."""
    b, h, w, c = x.shape
    xp = np.zeros((b, h + 2 * ph, w + 2 * pw, c), dtype=x.dtype)
    xp[:, ph : ph + h, pw : pw + w, :] = x
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    out = np.zeros((b, oh, ow, c * kh * kw), dtype=x.dtype)
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, i * sh : i * sh + kh, j * sw : j * sw + kw, :]
            # (b, kh, kw, c) -> channel-major (c, kh, kw)
            out[:, i, j, :] = patch.transpose(0, 3, 1, 2).reshape(b, -1)
    return out


def test_extract_patches_matches_naive():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 6, 6, 3).astype(np.float32)
    got = np.asarray(factors.extract_patches(jnp.asarray(x), (3, 3), (2, 2), ((1, 1), (1, 1))))
    want = _np_patches(x, 3, 3, 2, 2, 1, 1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_extract_patches_same_padding_string():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 5, 5, 4).astype(np.float32)
    got = factors.extract_patches(jnp.asarray(x), (3, 3), (1, 1), "SAME")
    assert got.shape == (2, 5, 5, 4 * 9)


def test_compute_a_dense_no_bias():
    rng = np.random.RandomState(2)
    a = rng.randn(16, 5).astype(np.float32)
    got = np.asarray(factors.compute_a_dense(jnp.asarray(a), has_bias=False))
    want = a.T @ (a / 16)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_compute_a_dense_bias_homogeneous_column():
    rng = np.random.RandomState(3)
    a = rng.randn(8, 5).astype(np.float32)
    got = np.asarray(factors.compute_a_dense(jnp.asarray(a), has_bias=True))
    ah = np.concatenate([a, np.ones((8, 1), np.float32)], 1)
    want = ah.T @ (ah / 8)
    assert got.shape == (6, 6)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # bias-bias entry is exactly 1 (mean of ones squared)
    np.testing.assert_allclose(got[-1, -1], 1.0, atol=1e-6)


def test_compute_a_dense_flattens_time_axis():
    rng = np.random.RandomState(4)
    a = rng.randn(4, 7, 5).astype(np.float32)  # [B, T, d] (RNN LM decoder)
    got = np.asarray(factors.compute_a_dense(jnp.asarray(a), has_bias=False))
    a2 = a.reshape(28, 5)
    want = a2.T @ (a2 / 28)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_compute_a_conv():
    rng = np.random.RandomState(5)
    x = rng.randn(3, 6, 6, 2).astype(np.float32)
    got = np.asarray(
        factors.compute_a_conv(
            jnp.asarray(x), (3, 3), (1, 1), ((1, 1), (1, 1)), has_bias=True
        )
    )
    p = _np_patches(x, 3, 3, 1, 1, 1, 1)  # [3, 6, 6, 18]
    spatial = 36
    p2 = p.reshape(-1, 18)
    p2 = np.concatenate([p2, np.ones((p2.shape[0], 1), np.float32)], 1)
    p2 = p2 / spatial
    want = p2.T @ (p2 / 3)
    assert got.shape == (19, 19)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_compute_g_dense_batch_averaged():
    rng = np.random.RandomState(6)
    g = rng.randn(16, 9).astype(np.float32)
    got = np.asarray(factors.compute_g_dense(jnp.asarray(g), batch_averaged=True))
    want = g.T @ (g * 16)
    np.testing.assert_allclose(got, want, atol=1e-4)
    got2 = np.asarray(factors.compute_g_dense(jnp.asarray(g), batch_averaged=False))
    want2 = g.T @ (g / 16)
    np.testing.assert_allclose(got2, want2, atol=1e-5)


def test_compute_g_conv():
    rng = np.random.RandomState(7)
    g = rng.randn(4, 5, 5, 6).astype(np.float32)  # NHWC output grads
    got = np.asarray(factors.compute_g_conv(jnp.asarray(g), batch_averaged=True))
    spatial = 25
    g2 = g.reshape(-1, 6) * 4 * spatial
    want = g2.T @ (g2 / (4 * spatial))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


def test_update_running_avg_code_semantics():
    # alpha weights HISTORY (reference code, not its docstring).
    cur = jnp.ones((3, 3))
    new = jnp.zeros((3, 3))
    out = factors.update_running_avg(new, cur, alpha=0.95)
    np.testing.assert_allclose(np.asarray(out), 0.95 * np.ones((3, 3)), atol=1e-7)


def test_conv_kernel_mat_roundtrip_and_patch_consistency():
    rng = np.random.RandomState(8)
    k = rng.randn(3, 3, 2, 4).astype(np.float32)  # HWIO
    mat = factors.conv_kernel_to_mat(jnp.asarray(k))
    assert mat.shape == (4, 18)
    back = factors.mat_to_conv_kernel(mat, k.shape)
    np.testing.assert_allclose(np.asarray(back), k, atol=1e-7)
    # conv(x, k) == patches(x) @ mat.T  — proves A's index space matches grads
    x = rng.randn(2, 5, 5, 2).astype(np.float32)
    y_conv = lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(k), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    p = factors.extract_patches(jnp.asarray(x), (3, 3), (1, 1), ((1, 1), (1, 1)))
    np.testing.assert_allclose(np.asarray(p @ mat.T), np.asarray(y_conv), atol=1e-4)


def test_grads_mat_roundtrip_dense_and_conv():
    rng = np.random.RandomState(9)
    gd = {"kernel": jnp.asarray(rng.randn(5, 7).astype(np.float32)),
          "bias": jnp.asarray(rng.randn(7).astype(np.float32))}
    mat = factors.grads_to_mat(gd)
    assert mat.shape == (7, 6)
    back = factors.mat_to_grads(mat, (5, 7), has_bias=True)
    np.testing.assert_allclose(np.asarray(back["kernel"]), np.asarray(gd["kernel"]), atol=1e-7)
    np.testing.assert_allclose(np.asarray(back["bias"]), np.asarray(gd["bias"]), atol=1e-7)

    gc = {"kernel": jnp.asarray(rng.randn(3, 3, 2, 4).astype(np.float32))}
    matc = factors.grads_to_mat(gc)
    assert matc.shape == (4, 18)
    backc = factors.mat_to_grads(matc, (3, 3, 2, 4), has_bias=False)
    np.testing.assert_allclose(np.asarray(backc["kernel"]), np.asarray(gc["kernel"]), atol=1e-7)


# ---------------------------------------------------------------------------
# The Gram helper: one product below _GRAM_MIN_SIDE, the column-block pairs on
# and above the diagonal from there on (two Pallas kernels, interpreted here)
# ---------------------------------------------------------------------------

_T = factors._GRAM_MIN_SIDE
_B = factors._GRAM_BLOCK
_HIGHEST = lax.Precision.HIGHEST


@pytest.fixture
def one_device(monkeypatch):
    """The blocked form is for programs on one device (a Mosaic call has no
    partitioning rule); the suite's platform has eight CPU devices."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def _today(kind, x, bias):
    """The expressions ops/factors.py held before the helper, word for word."""
    if kind == "a_dense":
        a = x.reshape(-1, x.shape[-1])
        n = a.shape[0]
        if bias:
            a = jnp.concatenate([a, jnp.ones((n, 1), dtype=a.dtype)], axis=1)
        return jnp.matmul(a.T, a / n, precision=_HIGHEST)
    if kind == "a_conv":  # 1x1 patches are the activations themselves
        p = x.reshape(-1, x.shape[-1])
        if bias:
            p = jnp.concatenate([p, jnp.ones((p.shape[0], 1), dtype=p.dtype)], axis=1)
        p = p / (x.shape[1] * x.shape[2])
        return jnp.matmul(p.T, p / x.shape[0], precision=_HIGHEST)
    if kind in ("g_dense", "g_dense_sum"):
        g = x.reshape(-1, x.shape[-1])
        n = g.shape[0]
        return jnp.matmul(g.T, g * n if kind == "g_dense" else g / n, precision=_HIGHEST)
    assert kind == "g_conv"
    gm = x.reshape(-1, x.shape[-1]) * x.shape[0] * (x.shape[1] * x.shape[2])
    return jnp.matmul(gm.T, gm / gm.shape[0], precision=_HIGHEST)


def _library(kind, x, bias):
    if kind == "a_dense":
        return factors.compute_a_dense(x, has_bias=bias)
    if kind == "a_conv":
        return factors.compute_a_conv(x, (1, 1), (1, 1), "VALID", has_bias=bias)
    if kind in ("g_dense", "g_dense_sum"):
        return factors.compute_g_dense(x, batch_averaged=kind == "g_dense")
    return factors.compute_g_conv(x, batch_averaged=True)


def _float64(kind, x, bias):
    """The same factor in float64 numpy: yT (y * s) with y = [x, 1] * pre."""
    y = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    rows = y.shape[0]
    if bias:
        y = np.concatenate([y, np.ones((rows, 1))], axis=1)
    if kind == "a_conv":
        return (y / (x.shape[1] * x.shape[2])).T @ (y / (x.shape[1] * x.shape[2]) / x.shape[0])
    if kind == "g_conv":
        y = y * x.shape[0] * x.shape[1] * x.shape[2]
    return y.T @ (y * rows if kind == "g_dense" else y / rows)


# side of the operand: below, just below, at, past the threshold; whole
# multiples of the 256-column block and not
@pytest.mark.parametrize("kind,lead,side,bias", [
    ("a_dense", (24,), 40, False),
    ("a_dense", (24,), 40, True),
    ("a_dense", (24,), _T - 1, True),
    ("a_dense", (24,), _T, False),
    ("a_dense", (24,), _T, True),
    ("a_dense", (3, 8), _T, True),  # [B, T, d]
    ("a_dense", (24,), _T + 1, True),
    ("a_dense", (24,), 1000, False),
    ("a_dense", (3, 8), 1280, True),
    ("a_dense", (24,), 1536, True),
    ("a_dense", (24,), 1700, False),
    ("a_conv", (2, 3, 4), 48, True),
    ("a_conv", (2, 3, 4), _T, True),
    ("a_conv", (2, 3, 4), 900, False),
    ("a_conv", (2, 3, 4), 900, True),
    ("g_dense", (24,), _T - 1, False),
    ("g_dense", (24,), _T, False),
    ("g_dense", (3, 8), 1100, False),
    ("g_dense_sum", (24,), 1024, False),
    ("g_conv", (2, 3, 4), 64, False),
    ("g_conv", (2, 3, 4), 800, False),
])
def test_gram_helper_against_float64_and_todays_expression(one_device, kind, lead, side, bias):
    x = jnp.asarray(np.random.RandomState(side).randn(*lead, side).astype(np.float32))
    got = np.asarray(_library(kind, x, bias))
    want = _float64(kind, x, bias)
    assert got.shape == want.shape == (side + bias, side + bias) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6 * np.abs(want).max())
    if side < _T:  # one product, as it always was: bit for bit
        assert factors._gram_tiles(24, side) is None
        np.testing.assert_array_equal(got, np.asarray(_today(kind, x, bias)))
    else:  # blocks on and above the diagonal, mirrored: exactly symmetric
        assert factors._gram_tiles(24, side) == (_B, 24)
        np.testing.assert_array_equal(got, got.T)
        if kind == "a_dense" and bias:
            assert got[-1, -1] == 1.0


@pytest.mark.parametrize("rows,tile", [(4096, 1024), (1536, 512), (384, 384), (1152, 128)])
def test_gram_blocks_sums_over_row_tiles(one_device, rows, tile):
    # several row tiles accumulate into one block; a side with an overhanging block
    assert factors._gram_tiles(rows, 800)[1] == tile
    x = np.random.RandomState(rows).randn(rows, 800).astype(np.float32)
    got = np.asarray(factors.gram_blocks(jnp.asarray(x), (("div", rows),), _B, tile, interpret=True))
    want = x.astype(np.float64).T @ (x.astype(np.float64) / rows)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6 * np.abs(want).max())
    np.testing.assert_array_equal(got, got.T)


@pytest.mark.parametrize("rows,side,devices", [
    (8192, _T - 128, 1),  # a narrow side
    (8192 + 64, 3072, 1),  # rows that no tile divides
    (8192, 3072, 8),  # a program over several devices: no partitioning rule
])
def test_gram_stays_one_product_where_the_blocks_do_not_apply(monkeypatch, rows, side, devices):
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert factors._gram_tiles(rows, side) is None
    jaxpr = jax.make_jaxpr(lambda g: factors.compute_g_dense(g, batch_averaged=True))(
        jax.ShapeDtypeStruct((rows, side), jnp.float32))
    assert "pallas_call" not in str(jaxpr) and str(jaxpr).count("dot_general") == 1


def test_blocked_product_issues_under_two_thirds_of_the_full_products_flops(one_device):
    # a count from the kernel's grid and block shapes, no timing: every step of
    # the first kernel multiplies a [tile, block]T by a [tile, block] at HIGHEST
    rows, side = 8192, 3072
    jaxpr = jax.make_jaxpr(lambda g: factors.compute_g_dense(g, batch_averaged=True))(
        jax.ShapeDtypeStruct((rows, side), jnp.float32))
    assert not any(e.primitive.name == "dot_general" for e in jaxpr.jaxpr.eqns)
    (inner,) = [e for e in jaxpr.jaxpr.eqns if e.params.get("name") == "gram_blocks"]
    calls = [e for e in inner.params["jaxpr"].jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    products = calls[0].params
    grid = products["grid_mapping"].grid
    dots = [e for e in products["jaxpr"].eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1 and dots[0].params["precision"] == (_HIGHEST, _HIGHEST)
    assert dots[0].params["preferred_element_type"] == jnp.float32
    (tile, block), rhs = (v.aval.shape for v in dots[0].invars)
    assert rhs == (tile, block) and dots[0].params["dimension_numbers"] == (((0,), (0,)), ((), ()))
    k = side // _B
    assert tuple(grid) == (k * (k + 1) // 2, rows // tile) and (int(tile), int(block)) == (1024, _B)
    macs = int(np.prod(grid)) * int(tile) * int(block) ** 2
    assert macs <= 0.65 * rows * side * side


class _Wide(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        return KFACDense(8, name="wide")(x)


def _traced_gauges(model, batch, **kfac_kw):
    """Trace (lower, nothing compiles) the `factors` program of `model`
    through the step builder with telemetry on; the two capture gauges."""
    kfac = KFAC(layers=capture.discover_layers(model, batch[0], train=True), damping=0.01, **kfac_kw)
    params = model.init(jax.random.PRNGKey(0), batch[0], train=True)["params"]
    tx = make_sgd(momentum=0.9)
    state = jax.eval_shape(lambda: TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), kfac_state=kfac.init(params)))
    step = make_train_step(model, tx, kfac, train_kwargs={"train": True})
    tel = get_telemetry()
    was = tel.enabled
    try:
        configure(enabled=True)
        step.lower(state, batch, jnp.float32(0.1), jnp.float32(0.01),
                   update_factors=True, update_eigen=False)
        return tel.gauges["kfac/capture_gram_blocked"], tel.gauges["kfac/capture_flops_share"]
    finally:
        configure(enabled=was)
        tel.reset()


def test_capture_gauges_tiny_lm_engages_nothing(one_device):
    model = transformer_lm.get_model(50, d_model=32, n_heads=2, n_layers=2)
    toks = np.random.RandomState(0).randint(0, 50, size=(4, 17))
    batch = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    assert _traced_gauges(model, batch, precond_method="inverse") == (0.0, 1.0)


def test_capture_gauges_3072_side_layer_under_two_thirds(one_device):
    batch = jnp.zeros((16, 3072), jnp.float32), jnp.zeros((16,), jnp.int32)
    blocked, share = _traced_gauges(_Wide(), batch)
    assert blocked == 1.0  # the A side (3072 + bias); the G side has 8 columns
    assert 0.5 < share < 0.65
