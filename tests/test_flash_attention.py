"""Pallas flash attention vs exact attention (interpreter mode — validates
the kernel's math on CPU; Mosaic compilation happens on real TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_pytorch_tpu.observability.telemetry import configure, get_telemetry
from kfac_pytorch_tpu.ops import flash_attention as fa
from kfac_pytorch_tpu.ops.flash_attention import best_attention_fn, flash_attention
from kfac_pytorch_tpu.parallel.context import full_attention


def _qkv(b=2, t=256, h=2, d=64, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.randn(b, t, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_matches_exact(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_multi_block_q_and_k():
    q, k, v = _qkv(t=512, seed=1)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=256, interpret=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_short_sequence_falls_back():
    q, k, v = _qkv(t=48, seed=2)  # not divisible by block → exact path
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_flow_through_kernel(causal):
    """Fused blockwise backward: dq/dk/dv must equal the exact path's."""
    q, k, v = _qkv(b=1, t=128, h=2, d=32, seed=4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    def loss_exact(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss_exact, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_gradients_multi_block_uneven():
    """Backward across multiple q AND k blocks with block_q != block_k."""
    q, k, v = _qkv(b=1, t=512, h=1, d=32, seed=5)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=128, block_k=256, interpret=True
            )
            * jnp.cos(jnp.arange(v.shape[-1]))
        )

    def loss_exact(q, k, v):
        return jnp.sum(
            full_attention(q, k, v, causal=True) * jnp.cos(jnp.arange(v.shape[-1]))
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss_exact, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def _loss_and_grads(attn, q, k, v):
    """Output and all three gradients under a weighting that no symmetry of
    the positions or the head dims leaves alone."""
    w = jnp.cos(jnp.arange(q.shape[1] * q.shape[3], dtype=jnp.float32)).reshape(
        1, q.shape[1], 1, q.shape[3])

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

    return attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_matches_exact(attn, causal, dtype=jnp.float32, t=1024, seed=8):
    q, k, v = (x.astype(dtype) for x in _qkv(b=1, t=t, h=2, d=64, seed=seed))
    exact = lambda q, k, v: full_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=causal)
    out, grads = _loss_and_grads(attn, q, k, v)
    ref, ref_grads = _loss_and_grads(exact, q, k, v)
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    # float32: the summation order alone differs; bfloat16 operands: the
    # kernel computes in float32 and rounds its results to the operands' type
    tol = dict(rtol=1e-4, atol=2e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    for got, want in zip((out, *grads), (ref, *ref_grads)):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_chosen_tiling_matches_exact(causal, dtype):
    """No blocks given: 1024 positions at heads of 64 take two q blocks of 512
    and two key sub-blocks a block, K and V resident; forward, dq, dk, dv."""
    tiling = fa._choose_blocks(1024, 64, jnp.dtype(dtype).itemsize)
    assert (tiling.block_q, tiling.block_k, tiling.major) == fa._choose_blocks(1024, 64, 4)[:3]
    assert 1024 // tiling.block_q > 1 and tiling.major == 1024
    attn = lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=True)
    _assert_matches_exact(attn, causal, dtype)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256), (256, 128), (512, 128), (256, 512)])
def test_explicit_blocks_match_exact(block_q, block_k):
    """Explicit blocks keep their meaning: the tile of logits one loop step
    forms, wider or narrower than the other; several sub-blocks a q block,
    and a key block that reaches past the diagonal of its q block."""
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k, interpret=True)
    _assert_matches_exact(attn, True)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("block_q,block_k,major", [
    (None, None, 512),  # the chosen blocks, two k-major blocks of one sub-block
    (128, 128, 256),  # four majors of two sub-blocks: dead majors before and after
    (256, 128, 512),  # a q block that spans sub-blocks of two majors
    (128, 256, 512),
])
def test_k_major_fallback_matches_exact(causal, block_q, block_k, major):
    """Where a head's K and V exceed the VMEM budget the third grid axis walks
    k-major (dk/dv: q-major) blocks with the carry in scratch; reached here by
    the private budget of `_choose_blocks`, which no public option sets."""
    budget = next(
        b for b in range(2**20, 64 * 2**20, 2**18)
        if fa._choose_blocks(1024, 64, 4, block_q, block_k, budget=b).major == major)
    tiling = fa._choose_blocks(1024, 64, 4, block_q, block_k, budget=budget)
    assert tiling.major == major < 1024
    attn = lambda q, k, v: fa._flash(q, k, v, causal, tiling, True)
    _assert_matches_exact(attn, causal)


@pytest.mark.parametrize("t,d,itemsize,resident", [
    (1024, 64, 4, True),  # gpt2_124m: 8 x 12 heads
    (2048, 256, 4, True),  # glm47_flash_ep8: 20 heads
    (128, 64, 4, True), (128, 64, 2, True),  # tests/test_chip_compile.py: one block
    (256, 64, 4, True), (512, 32, 4, True), (128, 32, 4, True),  # this file's
    (1024, 64, 2, True),
    (32768, 256, 4, False),  # the context-parallel users' lengths: k-major blocks
    (16384, 128, 2, False),
])
def test_choose_blocks(t, d, itemsize, resident):
    tiling = fa._choose_blocks(t, d, itemsize)
    block_q, block_k, major, limit = tiling
    assert t % block_q == 0 and t % block_k == 0 and block_k <= block_q
    assert block_q % 128 == 0 and block_k % 128 == 0
    assert t % major == 0 and major % block_q == 0 and major % block_k == 0
    assert (major == t) == resident
    need = fa._vmem_bytes(block_q, block_k, major, d, itemsize)
    assert need <= fa._VMEM_BUDGET and need <= limit <= 2 * fa._VMEM_BUDGET
    if not resident:  # the widest major that fits, not merely one that does
        assert fa._vmem_bytes(block_q, block_k, 2 * major, d, itemsize) > fa._VMEM_BUDGET


def test_choose_blocks_explicit_and_undividable():
    assert fa._choose_blocks(512, 32, 4, 128, 256)[:3] == (128, 256, 512)
    assert fa._choose_blocks(1024, 64, 4, block_q=256)[:2] == (256, 256)
    assert fa._choose_blocks(48, 64, 4) is None  # no block divides 48
    assert fa._choose_blocks(512, 64, 4, 128, 384) is None
    assert fa._choose_blocks(384, 64, 4)[:3] == (128, 128, 384)


def _flash_gauges(trace):
    """The four `attention/flash_*` gauges after `trace()` ran with telemetry on."""
    tel = get_telemetry()
    was = tel.enabled
    try:
        configure(enabled=True)
        trace()
        return {k.split("/")[1]: v for k, v in tel.gauges.items() if k.startswith("attention/")}
    finally:
        configure(enabled=was)
        tel.reset()


def _pallas_grids(jaxpr):
    """The grid of every pallas_call in a (closed) jaxpr, nested calls too."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}], ids=["chosen", "parent128"])
def test_gauges_read_what_the_traced_program_has(blocks):
    """GPT-2's shape, one layer's forward and backward: the four gauges
    against the grids of the pallas_calls in the traced program."""
    x = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True, **blocks))

    traced = []

    def trace():
        fa.reset_flash_tally()
        traced.append(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(x, x, x))

    gauges = _flash_gauges(trace)
    grids = _pallas_grids(traced[0].jaxpr)
    assert len(grids) == 3 == gauges["flash_calls"] == gauges["flash_kv_resident"]
    assert gauges["flash_grid_steps"] == sum(int(np.prod(g)) for g in grids)
    block_q = fa._choose_blocks(1024, 64, 4, **blocks).block_q
    if blocks:  # the parent's tiling: 96 x 8 x 8 tiles a call, 36 of 64 live
        assert gauges["flash_grid_steps"] == 3 * 96 * 8  # was 3 x 6,144 with the keys on the grid
        assert gauges["flash_live_share"] == 36 / 64
    else:
        assert gauges["flash_grid_steps"] == 3 * 96 * (1024 // block_q)
        nq = 1024 // block_q
        assert gauges["flash_live_share"] == (nq + 1) / (2 * nq)


@pytest.mark.parametrize("update_factors", [False, True], ids=["plain", "factors"])
def test_step_builder_resets_the_gauges_per_program(update_factors):
    """Through `make_train_step`: a two-layer LM's program holds six calls
    (forward, dq, dk/dv a layer), however many programs were traced before
    it and whatever `perturbation_zeros` traced on the way."""
    import functools

    from kfac_pytorch_tpu import KFAC, capture
    from kfac_pytorch_tpu.models import transformer_lm
    from kfac_pytorch_tpu.training import TrainState, make_train_step
    from kfac_pytorch_tpu.training.step import make_sgd

    attn = functools.partial(flash_attention, interpret=True)
    model = transformer_lm.get_model(50, max_len=128, d_model=64, n_heads=2, n_layers=2, attention_fn=attn)
    toks = np.random.RandomState(0).randint(0, 50, size=(2, 129))
    batch = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    kfac = KFAC(layers=capture.discover_layers(model, batch[0], train=True), damping=0.01, precond_method="inverse")
    params = model.init(jax.random.PRNGKey(0), batch[0], train=True)["params"]
    tx = make_sgd(momentum=0.9)
    state = jax.eval_shape(lambda: TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), kfac_state=kfac.init(params)))
    step = make_train_step(model, tx, kfac, train_kwargs={"train": True})

    def trace():
        for flag in (not update_factors, update_factors):  # the second program's counts start anew
            step.trace(state, batch, jnp.float32(0.1), jnp.float32(0.01),
                       update_factors=flag, update_eigen=False)

    gauges = _flash_gauges(trace)
    # 2 sequences x 2 heads, one block of 128 each way: a grid of (4, 1, 1) a call
    assert gauges == {"flash_calls": 6, "flash_grid_steps": 24, "flash_kv_resident": 6, "flash_live_share": 1.0}


_on_tpu = jax.devices()[0].platform == "tpu"


@pytest.mark.skipif(not _on_tpu, reason="needs a real TPU (Mosaic compile)")
def test_tpu_hardware_forward():
    """The kernel through Mosaic on a real chip, vs the exact jnp path."""
    q, k, v = _qkv(b=2, t=512, h=4, d=64, seed=6)
    out = flash_attention(q, k, v, causal=True)
    ref = full_attention(q, k, v, causal=True)
    # Both paths run bf16 MXU matmuls on real hardware but block/accumulate
    # in different orders, so they disagree by a few bf16 ULPs (eps ~7.8e-3)
    # on O(1) values — measured max |diff| 5.5e-3 over 2^18 elements. The
    # exact-math check is the interpreter test above (f32, tol 2e-5).
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


@pytest.mark.skipif(not _on_tpu, reason="needs a real TPU (Mosaic compile)")
def test_tpu_hardware_backward():
    q, k, v = _qkv(b=1, t=512, h=2, d=64, seed=7)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_exact(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss_exact, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, ge):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2
        )


@pytest.mark.skipif(not _on_tpu, reason="needs a real TPU (Mosaic compile)")
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_tpu_hardware_k_major_fallback(causal):
    """The k-major fallback through Mosaic on a real chip (no cell reaches
    it): four majors of 512 over 2048 positions, clamped index maps, the
    carry in scratch across the third grid axis; forward and gradients."""
    q, k, v = _qkv(b=1, t=2048, h=2, d=128, seed=9)
    budget = next(b for b in range(2**20, 64 * 2**20, 2**18)
                  if fa._choose_blocks(2048, 128, 4, budget=b).major == 512)
    tiling = fa._choose_blocks(2048, 128, 4, budget=budget)
    attn = lambda q, k, v: fa._flash(q, k, v, causal, tiling, False)
    exact = lambda q, k, v: full_attention(q, k, v, causal=causal)
    out, grads = _loss_and_grads(attn, q, k, v)
    ref, ref_grads = _loss_and_grads(exact, q, k, v)
    for got, want in zip((out, *grads), (ref, *ref_grads)):  # bf16 MXU passes on both sides
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_best_attention_fn_dispatch():
    # CPU → exact path; interpret=True → kernel (validated above)
    fn = best_attention_fn()
    assert fn is full_attention or jax.devices()[0].platform == "tpu"
    q, k, v = _qkv(t=128, seed=3)
    out = best_attention_fn(interpret=True)(q, k, v, causal=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
