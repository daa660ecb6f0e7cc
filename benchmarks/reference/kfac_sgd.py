"""Plain reference of one K-FAC-preconditioned SGD step, in ``jax.numpy``.

Independent of the program: imports nothing of ``kfac_pytorch_tpu``. It
follows the published algorithm as the configurations state it
(Martens & Grosse 2015; the factor statistics of HQ01/kfac_pytorch
``kfac/utils.py``; factored Tikhonov damping with the pi correction, section
6.3, for ``precond_method: inverse``):

    loss, g           forward and backward of the model
    g <- clip(g)      global-norm clip, where the configuration has one
    A, G              per K-FAC layer: input and output-gradient covariances,
                      running averages with weight ``stat_decay`` on history,
                      started from the identity            (capture steps)
    iA, iG            (A + pi sqrt(l) I)^-1, (G + sqrt(l)/pi I)^-1,
                      pi = sqrt((tr A / dim A) / (tr G / dim G))  (refresh steps)
    v = iG g iA       per layer, in the [out, in(+1)] layout   (every step)
    nu                min(1, sqrt(kl_clip / |sum v.g lr^2|)); g <- nu v
    SGD               u = g + wd p;  m = u + momentum m;  p <- p - lr m

Everything is float32 with matrix products at ``highest`` precision, unless
``prec`` (:class:`Precision`) says bfloat16: that is the control of the
output check, the same arithmetic at a lower precision (the inverses stay as
they are).

A model (``reference/<name>.py``) gives ``layers`` (name, kind, path, bias,
conv geometry) and ``loss(params, batch, tape, prec)``; its forward pass
(given ``prec`` for its operands and stored activations) calls
``tape.layer(name, x, y)`` at each K-FAC layer, which records the
layer's input and adds a zero perturbation to its output, so that one
``jax.grad`` yields the output gradients.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class Tape:
    def __init__(self, perts=None):
        self.perts = perts
        self.inputs = {}
        self.outputs = {}

    def layer(self, name, x, y):
        self.inputs[name] = x
        self.outputs[name] = y
        if self.perts is not None:
            y = y + self.perts[name].astype(y.dtype)
        return y


class Precision:
    """How the reference computes.

    ``float32``: operands as they are, products at ``highest``: the reference.
    ``bfloat16``: every operand of a matrix product or convolution, and every
    stored activation, in bfloat16: the control of the output check."""

    def __init__(self, name="float32"):
        if name not in ("float32", "bfloat16"):
            raise ValueError(f"unknown reference precision {name!r}")
        self.name = name
        self.matmul_precision = "default" if name == "bfloat16" else "highest"

    def operand(self, x):
        return x.astype(jnp.bfloat16 if self.name == "bfloat16" else jnp.float32)

    def store(self, x):
        return x.astype(jnp.bfloat16 if self.name == "bfloat16" else jnp.float32)


class RefState(NamedTuple):
    params: dict
    momentum: dict
    factors: dict   # name -> (A, G)
    inverses: dict  # name -> (iA, iG)


def get_path(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def set_path(tree, path, value):
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = set_path(tree[path[0]], path[1:], value)
    return out


def _mm(a, b, prec):
    """a @ b with float32 accumulation; operands as ``prec`` has them."""
    return jnp.matmul(prec.operand(a), prec.operand(b), preferred_element_type=jnp.float32)


def grad_matrix(layer, g):
    """A layer's gradient in the [out, in(+1)] layout."""
    k = g["kernel"]
    if layer["kind"] == "conv":
        kh, kw, cin, cout = k.shape
        mat = jnp.transpose(k, (3, 2, 0, 1)).reshape(cout, cin * kh * kw)
    else:
        mat = k.T
    if layer["bias"]:
        mat = jnp.concatenate([mat, g["bias"][:, None]], axis=1)
    return mat


def from_grad_matrix(layer, mat, like):
    out = {}
    if layer["bias"]:
        out["bias"] = mat[:, -1]
        mat = mat[:, :-1]
    if layer["kind"] == "conv":
        kh, kw, cin, cout = like["kernel"].shape
        out["kernel"] = jnp.transpose(mat.reshape(cout, cin, kh, kw), (2, 3, 1, 0))
    else:
        out["kernel"] = mat.T
    return out


def factor_stats(layer, x, gy, prec):
    """(A, G) of one layer from its input ``x`` and output gradient ``gy``
    (of a loss that is a mean over the rows)."""
    if layer["kind"] == "conv":
        b = x.shape[0]
        patches = lax.conv_general_dilated_patches(
            x, filter_shape=layer["kernel_size"], window_strides=layer["strides"],
            padding=layer["padding"], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # features ordered (channel, kh, kw), as grad_matrix's columns
        spatial = patches.shape[1] * patches.shape[2]
        p = patches.reshape(-1, patches.shape[-1]).astype(jnp.float32)
        if layer["bias"]:
            p = jnp.concatenate([p, jnp.ones((p.shape[0], 1), p.dtype)], axis=1)
        p = p / spatial
        a = _mm(p.T, p, prec) / b
        g = gy.reshape(-1, gy.shape[-1]).astype(jnp.float32) * (b * spatial)
        return a, _mm(g.T, g, prec) / g.shape[0]
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    n = xf.shape[0]
    if layer["bias"]:
        xf = jnp.concatenate([xf, jnp.ones((n, 1), xf.dtype)], axis=1)
    g = gy.reshape(-1, gy.shape[-1]).astype(jnp.float32)
    return _mm(xf.T, xf, prec) / n, _mm(g.T, g, prec) * n


NEWTON_SCHULZ_STEPS = 30


def spd_inverse(m):
    """``(inverse, residual)`` of a stack ``[k, n, n]`` of symmetric
    positive-definite matrices by the Newton-Schulz iteration
    ``X <- X (2I - M X)`` from ``X0 = I / ||M||_inf``, which converges for
    every such M (the eigenvalues of ``M X0`` lie in (0, 1]) and squares the
    error each step: 30 steps settle condition numbers up to 2^25. Matrix
    products only, so the TPU compiler takes seconds; a Cholesky for each of
    some two hundred factors took it minutes and over 20 GiB of host memory
    (PERF.md, Findings). The residual ``max_k ||I - M X||_F / sqrt(n)`` is
    returned so that the run can show the inverse is one."""
    eye = jnp.eye(m.shape[-1], dtype=m.dtype)
    x = eye / jnp.max(jnp.sum(jnp.abs(m), axis=-1), axis=-1)[:, None, None]
    x = lax.fori_loop(0, NEWTON_SCHULZ_STEPS, lambda _, x: jnp.matmul(x, 2 * eye - jnp.matmul(m, x)), x)
    resid = jnp.sqrt(jnp.sum(jnp.square(eye - jnp.matmul(m, x)), axis=(-1, -2)) / m.shape[-1])
    return x, jnp.max(resid)


def damped_inverses(facs, damping, eps=1e-10):
    """``({name: (iA, iG)}, residual)`` from ``{name: (A, G)}`` with the
    pi-corrected factored damping. Factors of one side are inverted as one
    stack; the residual is the largest over the stacks."""
    sqrt_l = jnp.sqrt(jnp.float32(damping))
    jobs = {}  # side -> [(name, 0 for A / 1 for G, damped matrix)]
    for name, (a, g) in facs.items():
        pi = jnp.sqrt(
            jnp.maximum(jnp.trace(a) / a.shape[0], eps)
            / jnp.maximum(jnp.trace(g) / g.shape[0], eps)
        )
        jobs.setdefault(a.shape[0], []).append(
            (name, 0, a + pi * sqrt_l * jnp.eye(a.shape[0], dtype=a.dtype)))
        jobs.setdefault(g.shape[0], []).append(
            (name, 1, g + sqrt_l / pi * jnp.eye(g.shape[0], dtype=g.dtype)))
    out = {name: [None, None] for name in facs}
    worst = jnp.float32(0.0)
    with jax.default_matmul_precision("highest"):
        for side in sorted(jobs):
            inv, resid = spd_inverse(jnp.stack([m for _, _, m in jobs[side]]))
            worst = jnp.maximum(worst, resid)
            for row, (name, which, _) in enumerate(jobs[side]):
                out[name][which] = inv[row]
    return {name: tuple(pair) for name, pair in out.items()}, worst


def init_state(model, params):
    facs, invs = {}, {}
    for layer in model.layers:
        k = get_path(params, layer["path"])["kernel"]
        a_side = (k.shape[0] * k.shape[1] * k.shape[2] if k.ndim == 4 else k.shape[0])
        a_side += int(layer["bias"])
        g_side = k.shape[-1]
        facs[layer["name"]] = (jnp.eye(a_side, dtype=jnp.float32), jnp.eye(g_side, dtype=jnp.float32))
        invs[layer["name"]] = (jnp.zeros((a_side, a_side), jnp.float32), jnp.zeros((g_side, g_side), jnp.float32))
    return RefState(params, jax.tree_util.tree_map(jnp.zeros_like, params), facs, invs)


def _loss_grads_stats(model, params, batch, prec, with_stats):
    """Loss, gradients and (on capture steps) factor statistics of ``batch``."""

    def shapes_fn(params):
        tape = Tape()
        model.loss(params, batch, tape, prec)
        return tape.outputs

    perts = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.float32), jax.eval_shape(shapes_fn, params)
    )

    def loss_fn(params, perts):
        tape = Tape(perts)
        return model.loss(params, batch, tape, prec), tape.inputs

    if not with_stats:
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, perts)[0])(params)
        return loss, grads, None
    (loss, inputs), (grads, gperts) = jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True
    )(params, perts)
    stats = {
        layer["name"]: factor_stats(layer, inputs[layer["name"]], gperts[layer["name"]], prec)
        for layer in model.layers
    }
    return loss, grads, stats


def loss_grads_stats(model, params, batch, prec, with_stats, row_blocks):
    """As above, the batch taken in ``row_blocks`` equal blocks of rows where
    the model's rows do not interact: the loss, the gradients and both
    covariances are means over rows, so they are the means over the blocks."""
    if row_blocks <= 1:
        return _loss_grads_stats(model, params, batch, prec, with_stats)
    if not model.rows_independent:
        raise ValueError("this model's rows interact (batch statistics): no row blocks")
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(row_blocks, a.shape[0] // row_blocks, *a.shape[1:]), batch
    )
    first = jax.tree_util.tree_map(lambda a: a[0], blocks)
    zero = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda p, b: _loss_grads_stats(model, p, b, prec, with_stats), params, first),
    )

    def body(acc, block):
        out = _loss_grads_stats(model, params, block, prec, with_stats)
        return jax.tree_util.tree_map(jnp.add, acc, out), None

    total, _ = lax.scan(body, zero, blocks)
    return jax.tree_util.tree_map(lambda a: a / row_blocks, total)


def forward_backward(model, hyper, state, batch, *, update_factors,
                     prec=Precision(), row_blocks=1):
    """The first half of a step, on the device: ``(loss, clipped gradients,
    factors)``, the factors' running averages moved on where
    ``update_factors``."""
    with jax.default_matmul_precision(prec.matmul_precision):
        loss, grads, stats = loss_grads_stats(
            model, state.params, batch, prec, update_factors, row_blocks
        )
        if hyper["grad_clip"]:
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
            scale = jnp.minimum(1.0, hyper["grad_clip"] / jnp.maximum(gnorm, 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        facs = state.factors
        if update_factors:
            d = hyper["stat_decay"]
            facs = {
                n: (d * facs[n][0] + (1 - d) * stats[n][0], d * facs[n][1] + (1 - d) * stats[n][1])
                for n in facs
            }
    return loss, grads, facs


def precondition_and_update(model, hyper, state, grads, facs, invs, lr, *,
                            prec=Precision()):
    """The second half, on the device: ``(state, gradients as the optimizer
    gets them)``: v = iG g iA per layer, the KL clip, then SGD with weight
    decay and momentum."""
    with jax.default_matmul_precision(prec.matmul_precision):
        updates, vg = {}, jnp.float32(0.0)
        for layer in model.layers:
            n = layer["name"]
            gm = grad_matrix(layer, get_path(grads, layer["path"]))
            v = _mm(_mm(invs[n][1], gm, prec), invs[n][0], prec)
            updates[n] = v
            vg = vg + jnp.sum(v * gm)
        nu = jnp.minimum(
            1.0, jnp.sqrt(hyper["kl_clip"] / jnp.maximum(jnp.abs(vg * lr**2), 1e-30))
        )
        for layer in model.layers:
            like = get_path(grads, layer["path"])
            new = from_grad_matrix(layer, updates[layer["name"]] * nu, like)
            grads = set_path(grads, layer["path"], {**like, **new})

        wd, mu = hyper["weight_decay"], hyper["momentum"]
        momentum = jax.tree_util.tree_map(
            lambda g, p, m: g + wd * p + mu * m, grads, state.params, state.momentum
        )
        params = jax.tree_util.tree_map(lambda p, m: p - lr * m, state.params, momentum)
    return RefState(params, momentum, facs, invs), grads
