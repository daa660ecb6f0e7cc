"""Fused Pallas apply kernels: eigenbasis precondition + SGD in one pass.

``ops/precondition.py::precondition_all`` hands XLA a chain of five batched
einsums per shape group — ``QGᵀ·grad·QA``, the damped eigenvalue divide,
and the two back-rotations — and the optimizer step is a SEPARATE optax
pass over every parameter leaf (``training/step.py``): each stage writes
its intermediate to HBM and the next reads it back (what that costs on
the chip: not measured). The kernels here fuse each stage chain into one
VMEM-resident pass. The v5e compiler refuses ``fused_precondition_stack``
at most ResNet-50 shapes today (docs/PERF.md, "Refused by the v5e
compiler"), so the kernels are an explicit opt-in:

* :func:`fused_precondition_stack` — one grid step per layer of a shape
  group holds the layer's ``[g, a]`` gradient and its ``QA``/``QG`` bases
  in VMEM, runs the whole rotate → damped-divide → back-rotate chain on
  the MXU without materializing any intermediate in HBM, and accumulates
  the KL-clip inner product ``Σ v·g`` as a per-layer scalar by-product
  (the dense path recomputes it from HBM afterwards —
  ``kl_clip_coefficient``).
* :func:`fused_sgd_apply` — the momentum + weight-decay SGD update
  (``m' = μ·m + g + wd·p``; ``p' = p − lr·m'``) over ALL parameter leaves
  flattened into one ``[rows, 128]`` stream: one kernel, one read and one
  write per state buffer, replacing the per-leaf optax ``tx.update`` +
  ``apply_updates`` pass.

The dense path stays untouched as the verbatim parity oracle
(tests/test_fused_apply.py pins ``rtol 1e-6`` agreement in interpret
mode). ``interpret=True`` (automatic on the CPU backend) is how CPU tier-1 validates
the kernel math, same contract as ``ops/factor_kernels.py``.

Dispatch: the preconditioner routes through
:func:`dispatch_precondition_stack` / the train step through
:func:`dispatch_sgd_apply`, both keyed on the ambient
:func:`apply_kernel_scope` ("dense" unless a train step opened a "pallas"
scope from ``KFAC(apply_kernel=...)``). Shape-only tracing
(``jax.eval_shape`` of the step, compile-cache discovery) never opens a
scope, so it pins "dense" — the scope is trace-time state, exactly like
``factor_kernel_scope``. Low-rank (Woodbury) and streaming entries, the
embedding diagonal-A form, and the distributed/owner solve paths stay on
the dense apply (see ``precondition_all_with_vg``); the planner's
validity rules mirror the same coverage.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kfac_pytorch_tpu import compat
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.observability.telemetry import get_telemetry

PyTree = Any

APPLY_KERNELS = ("auto", "pallas", "dense")

# Fused-SGD stream tiling: 128 lanes (the TPU lane width) and enough rows
# per grid step that each block is a few hundred KB — small against VMEM,
# large enough that grid overhead vanishes.
_SGD_LANES = 128
_SGD_BLOCK_ROWS = 256


# ---------------------------------------------------------------------------
# Kernel-selection scope
# ---------------------------------------------------------------------------

_ACTIVE_APPLY = "dense"


def resolve_apply_kernel(kind: str) -> str:
    """``auto`` → dense on every backend; validate explicit kinds.

    The fused apply kernel is refused by the v5e compiler for every
    multi-layer shape group and for ResNet-50's large layers (docs/PERF.md,
    "Refused by the v5e compiler"), so it is opt-in: an explicit
    ``"pallas"`` compiles or raises the compiler's own error — nothing
    catches it and nothing gives way to dense.
    """
    if kind not in APPLY_KERNELS:
        raise ValueError(
            f"Invalid apply_kernel: {kind!r} (choose from {APPLY_KERNELS})"
        )
    return "dense" if kind == "auto" else kind


def active_apply_kernel() -> str:
    """The kernel kind dispatchers currently route to ("pallas"/"dense")."""
    return _ACTIVE_APPLY


@contextlib.contextmanager
def apply_kernel_scope(kind: str):
    """Route the fused-apply dispatchers inside the block.

    Train steps open this around ``KFAC.update`` (and the optimizer step)
    at TRACE time — the body of a jitted function runs as Python during
    tracing — so the preconditioner picks the kernel the
    ``KFAC(apply_kernel=...)`` config asked for without threading a flag
    through every solve signature. Scopes nest; anything traced outside a
    scope (``jax.eval_shape`` shape discovery, state templates) pins
    "dense".
    """
    global _ACTIVE_APPLY
    prev = _ACTIVE_APPLY
    _ACTIVE_APPLY = resolve_apply_kernel(kind)
    try:
        yield
    finally:
        _ACTIVE_APPLY = prev


def _default_interpret(interpret: Optional[bool]) -> bool:
    """Compile on a TPU, interpret on the CPU backend the tests force,
    refuse any other backend (there is no Pallas-TPU lowering for it)."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas-TPU kernels compile on a TPU and interpret on the CPU "
            f"backend only; default backend is {backend!r}"
        )
    return backend == "cpu"


# ---------------------------------------------------------------------------
# Fused eigenbasis apply: rotate → damped divide → back-rotate → Σ v·g
# ---------------------------------------------------------------------------


def _fused_apply_kernel(gm_ref, qa_ref, da_ref, qg_ref, dg_ref, damp_ref,
                        out_ref, vg_ref):
    """One grid step: the whole eigenbasis solve of ONE layer, in VMEM.

    Grid = (k,) over the stack rows (the layers of one shape group). All
    five matmuls chain through VMEM values — the ``v1``/``v2``
    intermediates of the dense einsum path never exist in HBM — and the
    damped eigenvalue denominator is built as a rank-1 MXU outer product
    ``dGᵀ·dA`` (no relayout of the eigenvalue vectors needed). The KL-clip
    partial ``Σ v·g`` rides out as a per-layer scalar so the caller never
    re-reads ``v``/``g`` from HBM just to reduce them.
    """
    g = gm_ref[0]  # [go, ai]
    qa = qa_ref[0].astype(jnp.float32)  # [ai, ai]
    qg = qg_ref[0].astype(jnp.float32)  # [go, go]
    dgv = dg_ref[...]  # [1, go]
    dav = da_ref[...]  # [1, ai]
    lam = damp_ref[0, 0]
    # v1 = QGᵀ · g · QA
    t = jax.lax.dot_general(
        qg, g, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    t = jax.lax.dot_general(
        t, qa, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # v2 = v1 / (dG dAᵀ + λ): the outer product is a [go,1]x[1,ai] matmul
    denom = jax.lax.dot_general(
        dgv, dav, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    t = t / (denom + lam)
    # v = QG · v2 · QAᵀ
    v = jax.lax.dot_general(
        qg, t, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    v = jax.lax.dot_general(
        v, qa, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out_ref[...] = v[None]
    vg_ref[...] = jnp.sum(v * g).reshape(1, 1)


def fused_precondition_stack(
    gm: jnp.ndarray,
    qa: jnp.ndarray,
    da: jnp.ndarray,
    qg: jnp.ndarray,
    dg: jnp.ndarray,
    damping: jnp.ndarray,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ``precondition_all`` einsum chain for one shape group.

    ``gm``: stacked ``[k, g, a]`` f32 gradient matrices; ``qa``/``qg`` the
    stacked eigenvector matrices (any float dtype — upcast to f32 in VMEM,
    mirroring the dense path's f32 accumulate under
    ``_ROTATION_PRECISION``); ``da``/``dg`` the stacked f32 eigenvalues;
    ``damping`` a traced scalar. Returns ``(v [k, g, a] f32, vg [k] f32)``
    with ``vg[i] = Σ v_i·g_i`` — the per-layer KL-clip partial the caller
    folds into ``kl_clip_from_vg``.
    """
    k, go, ai = gm.shape
    damp = jnp.asarray(damping, jnp.float32).reshape(1, 1)
    out, vg = pl.pallas_call(
        _fused_apply_kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, go, ai), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, ai, ai), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, ai), lambda i: (i, 0)),
            pl.BlockSpec((1, go, go), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, go), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, go, ai), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, go, ai), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",),
        ),
        interpret=_default_interpret(interpret),
    )(
        gm.astype(jnp.float32),
        qa,
        da.astype(jnp.float32),
        qg,
        dg.astype(jnp.float32),
        damp,
    )
    return out, vg[:, 0]


def dispatch_precondition_stack(
    gm: jnp.ndarray,
    qa: jnp.ndarray,
    da: jnp.ndarray,
    qg: jnp.ndarray,
    dg: jnp.ndarray,
    damping: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Route one shape group's fused apply per the ambient kernel scope.

    Only called from the pallas branch of ``precondition_all_with_vg`` —
    the dense branch keeps the verbatim einsum chain — so this records the
    choice and cuts the tangent path (the apply is an optimizer-side
    consumer of already-stopped gradients; ``stop_gradient`` keeps autodiff
    of any enclosing program from needing a ``pallas_call`` JVP rule).
    """
    tel = get_telemetry()
    tel.set_gauge("kfac/apply_kernel", 1.0)
    with phase("kfac_apply", "trace/kfac/apply_kernel"):
        return fused_precondition_stack(
            jax.lax.stop_gradient(gm),
            jax.lax.stop_gradient(qa),
            jax.lax.stop_gradient(da),
            jax.lax.stop_gradient(qg),
            jax.lax.stop_gradient(dg),
            damping,
        )


# ---------------------------------------------------------------------------
# Fused SGD: momentum + weight decay + parameter update, one stream
# ---------------------------------------------------------------------------


def _fused_sgd_kernel(p_ref, g_ref, m_ref, lr_ref, newp_ref, newm_ref,
                      *, mu, wd):
    """One grid step: torch-order SGD on one ``[rows, 128]`` block.

    ``m' = μ·m + (g + wd·p); p' = p − lr·m'`` — weight decay folds into
    the (preconditioned) gradient BEFORE momentum, then the lr scaling,
    the exact composition ``training.step.make_sgd`` builds from optax
    (add_decayed_weights → trace → −lr·apply). Zero-padded tail elements
    map to zero outputs, so the caller's unpad slice is exact.
    """
    p = p_ref[...]
    g = g_ref[...]
    m = m_ref[...]
    lr = lr_ref[0, 0]
    m2 = mu * m + (g + wd * p)
    newm_ref[...] = m2
    newp_ref[...] = p - lr * m2


def fused_sgd_apply(
    params: PyTree,
    grads: PyTree,
    trace: PyTree,
    lr: jnp.ndarray,
    momentum: float,
    weight_decay: float,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[PyTree, PyTree]:
    """The whole SGD step as ONE flattened Pallas stream.

    Every leaf of ``params``/``grads``/``trace`` (the optax ``TraceState``
    momentum pytree — same structure as params) ravels into one f32
    ``[rows, 128]`` stream; a single kernel pass produces the updated
    parameters and momentum. Returns ``(new_params, new_trace)`` with the
    input structures and dtypes. Replaces the per-leaf
    ``tx.update → −lr → optax.apply_updates`` chain bit-for-bit up to f32
    reassociation (the math per element is identical; tier-1 pins parity).
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    gleaves = treedef.flatten_up_to(grads)
    mleaves = treedef.flatten_up_to(trace)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np_prod(s)) for s in shapes]
    n = sum(sizes)

    def _pack(ls):
        return jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in ls]
        )

    block = _SGD_BLOCK_ROWS * _SGD_LANES
    padded = -(-max(n, 1) // block) * block
    rows = padded // _SGD_LANES

    def _grid_form(flat):
        return jnp.pad(flat, (0, padded - n)).reshape(rows, _SGD_LANES)

    pflat = _grid_form(_pack(leaves))
    gflat = _grid_form(_pack(gleaves))
    mflat = _grid_form(_pack(mleaves))
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)

    kernel = functools.partial(
        _fused_sgd_kernel, mu=float(momentum), wd=float(weight_decay)
    )
    newp, newm = pl.pallas_call(
        kernel,
        grid=(rows // _SGD_BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((_SGD_BLOCK_ROWS, _SGD_LANES), lambda i: (i, 0)),
            pl.BlockSpec((_SGD_BLOCK_ROWS, _SGD_LANES), lambda i: (i, 0)),
            pl.BlockSpec((_SGD_BLOCK_ROWS, _SGD_LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_SGD_BLOCK_ROWS, _SGD_LANES), lambda i: (i, 0)),
            pl.BlockSpec((_SGD_BLOCK_ROWS, _SGD_LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _SGD_LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, _SGD_LANES), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",),
        ),
        interpret=_default_interpret(interpret),
    )(pflat, gflat, mflat, lr2)

    def _unpack(flat, like_dtypes) -> List[jnp.ndarray]:
        flat = flat.reshape(-1)[:n]
        out, off = [], 0
        for shape, size, dt in zip(shapes, sizes, like_dtypes):
            out.append(flat[off:off + size].reshape(shape).astype(dt))
            off += size
        return out

    new_params = jax.tree_util.tree_unflatten(treedef, _unpack(newp, dtypes))
    new_trace = jax.tree_util.tree_unflatten(
        treedef, _unpack(newm, [l.dtype for l in mleaves])
    )
    return new_params, new_trace


def dispatch_sgd_apply(
    params: PyTree,
    grads: PyTree,
    trace: PyTree,
    lr: jnp.ndarray,
    momentum: float,
    weight_decay: float,
) -> Optional[Tuple[PyTree, PyTree]]:
    """Route the optimizer step per the ambient apply-kernel scope.

    Returns ``None`` when the scope is dense — the caller then runs the
    untouched optax chain, keeping the default program HLO-identical.
    """
    tel = get_telemetry()
    kind = active_apply_kernel()
    tel.set_gauge("kfac/apply_kernel", 1.0 if kind == "pallas" else 0.0)
    if kind != "pallas":
        return None
    with phase("optimizer", "trace/kfac/apply_kernel"):
        return fused_sgd_apply(
            jax.lax.stop_gradient(params),
            jax.lax.stop_gradient(grads),
            jax.lax.stop_gradient(trace),
            lr,
            momentum,
            weight_decay,
        )


def np_prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out
