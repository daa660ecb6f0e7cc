"""Fused Pallas patch-covariance kernel tests (ops/factor_kernels.py).

The dense im2col path (ops/factors.py::compute_a_conv) is the parity
oracle: the fused kernel computes the same A factor up to f32 summation
order (it accumulates raw products per offset-pair tile and applies one
fused 1/(spatial²·B) scale, where the oracle divides the patch matrix by
spatial before a single HIGHEST-precision matmul), so parity is tight
allclose, not bitwise. All kernel runs here use interpret=True — the
Pallas interpreter on CPU, same contract as tests/test_flash_attention.py
(scripts/check_pallas_interpret.py lints that this stays true for every
pallas_call in ops/).

The memory-regression test compiles (never executes) the ResNet-50
stage-1 conv factor computation at batch 128 and asserts the fused
program's XLA temp footprint sits under the dense path's — the im2col
materialization (~925 MB, docs/PERF.md "Factor-statistics memory") is the
thing this kernel exists to delete.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_pytorch_tpu import KFAC, capture
from kfac_pytorch_tpu.models.layers import KFACConv, KFACDense
from kfac_pytorch_tpu.observability import telemetry as tel_mod
from kfac_pytorch_tpu.ops import factor_kernels, factors
from kfac_pytorch_tpu.training.step import TrainState, make_sgd, make_train_step


def _acts(shape, seed=0):
    r = np.random.RandomState(seed)
    return jnp.asarray(r.randn(*shape).astype(np.float32))


# (shape BHWC, kernel_size, strides, padding, dilation, has_bias)
PARITY_CASES = [
    # pointwise conv: kk == 1, no patch overlap at all
    ((4, 8, 8, 8), (1, 1), (1, 1), "VALID", (1, 1), False),
    # the workhorse: 3x3 SAME stride 1, with the fused bias column
    ((4, 9, 9, 8), (3, 3), (1, 1), "SAME", (1, 1), True),
    # strided VALID (downsampling convs)
    ((4, 10, 10, 4), (3, 3), (2, 2), "VALID", (1, 1), True),
    # large window: ResNet stem geometry, SAME + stride 2 (odd split pads)
    ((2, 12, 12, 4), (7, 7), (2, 2), "SAME", (1, 1), False),
    # explicit asymmetric padding pairs
    ((4, 8, 8, 4), (3, 3), (1, 1), ((1, 2), (0, 1)), (1, 1), True),
    # dilated (atrous) window, SAME resolution must match the oracle's
    ((2, 11, 11, 4), (3, 3), (1, 1), "SAME", (2, 2), True),
    # rectangular kernel + anisotropic stride/dilation
    ((4, 10, 12, 4), (2, 3), (2, 1), "VALID", (1, 2), False),
    # odd channel count: C·kh·kw = 45 — no lane-friendly tiling exists,
    # the divisor plan must still be exact
    ((4, 8, 8, 5), (3, 3), (1, 1), "SAME", (1, 1), True),
    # batch not a multiple of any pallas-ish block size
    ((3, 8, 8, 8), (3, 3), (1, 1), "SAME", (1, 1), False),
]


@pytest.mark.parametrize(
    "shape,ksize,strides,padding,dilation,bias", PARITY_CASES
)
def test_fused_matches_dense_oracle(shape, ksize, strides, padding, dilation, bias):
    x = _acts(shape)
    want = factors.compute_a_conv(
        x, ksize, strides, padding, bias, kernel_dilation=dilation
    )
    got = factor_kernels.compute_a_conv_fused(
        x, ksize, strides, padding, bias, kernel_dilation=dilation,
        interpret=True,
    )
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("groups", [2, 4])
def test_grouped_fused_matches_dense_oracle(groups):
    x = _acts((4, 8, 8, 8), seed=3)
    want = factors.compute_a_conv_grouped(
        x, groups, (3, 3), (1, 1), "SAME", True, kernel_dilation=(1, 1)
    )
    got = factor_kernels.compute_a_conv_grouped_fused(
        x, groups, (3, 3), (1, 1), "SAME", True, kernel_dilation=(1, 1),
        interpret=True,
    )
    assert got.shape == (groups,) + want.shape[1:]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_fused_under_jit_and_stop_gradient():
    """The dispatch path's exact usage: jitted, behind stop_gradient, while a
    surrounding value_and_grad differentiates the activations."""
    x = _acts((4, 8, 8, 4), seed=5)

    def loss(x):
        a = factor_kernels.compute_a_conv_fused(
            jax.lax.stop_gradient(x), (3, 3), (1, 1), "SAME", True,
            interpret=True,
        )
        return jnp.sum(x) + 0.0 * jnp.sum(a), a

    (val, a), g = jax.jit(
        lambda x: jax.value_and_grad(loss, has_aux=True)(x)
    )(x)
    want = factors.compute_a_conv(x, (3, 3), (1, 1), "SAME", True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g), np.ones_like(x), rtol=1e-6)


def test_resolve_and_scope():
    assert factor_kernels.resolve_factor_kernel("dense") == "dense"
    assert factor_kernels.resolve_factor_kernel("pallas") == "pallas"
    assert factor_kernels.resolve_factor_kernel("auto") == "dense"
    with pytest.raises(ValueError):
        factor_kernels.resolve_factor_kernel("im2col")

    assert factor_kernels.active_factor_kernel() == "dense"
    with factor_kernels.factor_kernel_scope("pallas"):
        assert factor_kernels.active_factor_kernel() == "pallas"
        with factor_kernels.factor_kernel_scope("dense"):
            assert factor_kernels.active_factor_kernel() == "dense"
        assert factor_kernels.active_factor_kernel() == "pallas"
    assert factor_kernels.active_factor_kernel() == "dense"
    # the scope must restore even when the body raises
    with pytest.raises(RuntimeError):
        with factor_kernels.factor_kernel_scope("pallas"):
            raise RuntimeError("boom")
    assert factor_kernels.active_factor_kernel() == "dense"


def test_dispatch_routes_and_records_gauge():
    tel = tel_mod.configure(enabled=True)
    try:
        x = _acts((2, 6, 6, 4), seed=7)
        want = factors.compute_a_conv(x, (3, 3), (1, 1), "SAME", False)
        with factor_kernels.factor_kernel_scope("pallas"):
            got = factor_kernels.dispatch_compute_a_conv(
                x, (3, 3), (1, 1), "SAME", False
            )
        assert tel.snapshot()["gauges"]["kfac/factor_kernel"] == 1.0
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        got_d = factor_kernels.dispatch_compute_a_conv(
            x, (3, 3), (1, 1), "SAME", False
        )
        assert tel.snapshot()["gauges"]["kfac/factor_kernel"] == 0.0
        np.testing.assert_allclose(np.asarray(got_d), np.asarray(want))
    finally:
        tel_mod.configure(enabled=False)
        tel.reset()


class _ConvNet(nn.Module):
    """Plain + grouped conv + dense head: every dispatcher fires once."""

    @nn.compact
    def __call__(self, x, train=True):
        x = KFACConv(8, (3, 3), use_bias=True)(x)
        x = nn.relu(x)
        x = KFACConv(8, (3, 3), strides=(2, 2), feature_group_count=2)(x)
        x = nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return KFACDense(10)(x)


def _run_one_step(factor_kernel):
    model = _ConvNet()
    tx = make_sgd(momentum=0.0)
    r = np.random.RandomState(11)
    x = jnp.asarray(r.randn(4, 8, 8, 4).astype(np.float32))
    y = jnp.asarray(r.randint(0, 10, size=4))
    kfac = KFAC(damping=0.01, fac_update_freq=1, kfac_update_freq=1,
                factor_kernel=factor_kernel,
                layers=capture.discover_layers(model, x, train=True))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(variables["params"]),
        kfac_state=kfac.init(variables["params"]),
    )
    step = make_train_step(model, tx, kfac, train_kwargs={"train": True})
    state, metrics = step(
        state, (x, y), jnp.float32(0.05), jnp.float32(0.01),
        update_factors=True, update_eigen=True,
    )
    return jax.device_get(state)


def test_train_step_pallas_matches_dense_end_to_end():
    """KFAC(factor_kernel='pallas') through the real jitted train step —
    factors AND the preconditioned update must track the dense run."""
    s_pal = _run_one_step("pallas")
    s_den = _run_one_step("dense")
    fa, fd = s_pal.kfac_state["factors"], s_den.kfac_state["factors"]
    assert set(fa.keys()) == set(fd.keys())
    for name in fd:
        for side in ("A", "G"):
            if side in fd[name]:
                np.testing.assert_allclose(
                    np.asarray(fa[name][side]), np.asarray(fd[name][side]),
                    rtol=2e-5, atol=2e-5, err_msg=f"{name}/{side}",
                )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_pal.params),
        jax.tree_util.tree_leaves(s_den.params),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# (batch, seqlen, vocab) — chosen to hit every padding corner of the
# token-gather kernel: n·t not a multiple of the 1024 token block, vocab
# not a multiple of the 512 tile, single blocks, and the aligned case.
EMBED_PARITY_CASES = [
    (6, 7, 11),        # tiny: one token block, one vocab tile
    (3, 700, 37),      # n=2100 spans 3 token blocks, ragged tail
    (4, 50, 777),      # vocab spans 2 tiles with a ragged tail
    (2, 1100, 1030),   # both axes ragged at once
    (2, 512, 512),     # exactly block/tile aligned
]


@pytest.mark.parametrize("batch,seqlen,vocab", EMBED_PARITY_CASES)
def test_embed_fused_matches_scatter_oracle_bitwise(batch, seqlen, vocab):
    """Token-gather kernel vs the scatter-add oracle. Both accumulate
    integer counts in f32 and divide once by N, so parity is BITWISE —
    any drift means the sentinel/padding plan leaked counts."""
    r = np.random.RandomState(batch * 1000 + vocab)
    ids = jnp.asarray(r.randint(0, vocab, size=(batch, seqlen)).astype(np.int32))
    want = factors.compute_a_embed(ids, vocab)
    got = factor_kernels.compute_a_embed_fused(ids, vocab, interpret=True)
    assert got.shape == want.shape == (vocab,) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ...and both agree with the dense one-hot diagonal it stands in for
    dense = factors.compute_a_embed_onehot(ids, vocab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-6, atol=1e-7)


def test_embed_fused_under_jit():
    """Jitted, int ids (no tangent — the dispatcher never wraps these in
    stop_gradient), 1-D ids accepted like the oracle."""
    r = np.random.RandomState(21)
    ids = jnp.asarray(r.randint(0, 91, size=(130,)).astype(np.int32))
    got = jax.jit(
        lambda i: factor_kernels.compute_a_embed_fused(i, 91, interpret=True)
    )(ids)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(factors.compute_a_embed(ids, 91)))


def test_embed_dispatch_routes_and_records_gauge():
    tel = tel_mod.configure(enabled=True)
    try:
        r = np.random.RandomState(23)
        ids = jnp.asarray(r.randint(0, 33, size=(4, 9)).astype(np.int32))
        want = factors.compute_a_embed(ids, 33)
        with factor_kernels.factor_kernel_scope("pallas"):
            got = factor_kernels.dispatch_compute_a_embed(ids, 33)
        assert tel.snapshot()["gauges"]["kfac/embedding_capture_kernel"] == 1.0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        got_d = factor_kernels.dispatch_compute_a_embed(ids, 33)
        assert tel.snapshot()["gauges"]["kfac/embedding_capture_kernel"] == 0.0
        np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want))
    finally:
        tel_mod.configure(enabled=False)
        tel.reset()


def test_embed_fused_compiled_memory_beats_one_hot():
    """Compile-only: the [B·T, V] one-hot (and the [V, V] dense A it feeds)
    must never exist on the fused path. 16×512 tokens over a 4096 vocab put
    the one-hot temporary at 128 MB; the kernel streams token blocks."""
    vocab, toks = 4096, (16, 512)
    ids = jax.ShapeDtypeStruct(toks, jnp.int32)
    fused = jax.jit(
        lambda i: factor_kernels.compute_a_embed_fused(i, vocab, interpret=True)
    )
    dense = jax.jit(lambda i: factors.compute_a_embed_onehot(i, vocab))
    m_fused = fused.lower(ids).compile().memory_analysis()
    m_dense = dense.lower(ids).compile().memory_analysis()
    if m_fused is None or m_dense is None:
        pytest.skip("backend does not report compiled memory stats")
    one_hot_bytes = toks[0] * toks[1] * vocab * 4
    assert m_dense.temp_size_in_bytes >= one_hot_bytes, (
        "one-hot oracle no longer materializes [B·T, V] — update this test"
    )
    assert m_fused.temp_size_in_bytes * 10 < m_dense.temp_size_in_bytes, (
        f"fused temp {m_fused.temp_size_in_bytes} not 10x below dense "
        f"{m_dense.temp_size_in_bytes}"
    )


def test_fused_compiled_memory_beats_dense_im2col():
    """ResNet-50 stage-1 geometry at the batch-128 lever: [128,56,56,64] 3x3
    SAME. Compile-only (memory_analysis never executes), so the dense arm's
    925 MB patch temporary is observed, not allocated."""
    shape = (128, 56, 56, 64)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)

    dense = jax.jit(
        lambda a: factors.compute_a_conv(a, (3, 3), (1, 1), "SAME", True)
    )
    fused = jax.jit(
        lambda a: factor_kernels.compute_a_conv_fused(
            a, (3, 3), (1, 1), "SAME", True, interpret=True
        )
    )
    m_dense = dense.lower(x).compile().memory_analysis()
    m_fused = fused.lower(x).compile().memory_analysis()
    if m_dense is None or m_fused is None:
        pytest.skip("backend does not report compiled memory stats")

    patch_bytes = 128 * 56 * 56 * (64 * 9) * 4  # the im2col temporary
    assert m_dense.temp_size_in_bytes >= patch_bytes, (
        "oracle no longer materializes im2col — this regression test and "
        "docs/PERF.md need updating"
    )
    assert m_fused.temp_size_in_bytes < m_dense.temp_size_in_bytes, (
        f"fused temp {m_fused.temp_size_in_bytes} not below dense "
        f"{m_dense.temp_size_in_bytes}"
    )
    # the headline claim: the fused program needs no O(B·OH·OW·C·kh·kw) temp
    assert m_fused.temp_size_in_bytes < patch_bytes // 2


@pytest.mark.parametrize("backend", ["tpu", "cpu", "gpu"])
def test_auto_is_dense_on_every_backend(monkeypatch, backend):
    """The Pallas capture kernels are opt-in: the v5e compiler refuses them
    at ResNet-50 shapes (docs/PERF.md), so "auto" never picks them."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert factor_kernels.resolve_factor_kernel("auto") == "dense"
    from kfac_pytorch_tpu import KFAC

    assert KFAC(damping=0.01).factor_kernel == "dense"


def test_default_interpret_by_backend(monkeypatch):
    """Compile on a TPU, interpret on the tests' CPU backend, refuse the
    rest; an explicit argument is taken as given."""
    assert factor_kernels._default_interpret(None) is True  # CPU tier-1
    assert factor_kernels._default_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert factor_kernels._default_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        factor_kernels._default_interpret(None)
