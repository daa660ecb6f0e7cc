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

Layer kinds: ``dense`` (a ``kernel`` leaf ``[a, m]``), ``conv``
(``[kh, kw, cin, cout]``) and ``bank``: a ``kernel`` leaf ``[E, a, m]`` of E
experts, no bias, whose forward pass calls ``tape.layer(name, x, y, rows=r)``
with ``y`` ``[T, E, m]`` (every expert's output for every row, of which the
model uses the routed ones), ``r`` ``[T, E]`` in {0, 1} (row t is routed to
expert e) and ``x`` the input all experts share, ``[T, a]``, or each
expert's own, ``[T, E, a]`` (a bank fed by another bank). Its statistics are
those of E dense layers over all T rows whose unrouted rows are zero:

    A_e = (1/T) sum_t r_te x_t x_t^T        G_e = T sum_t g_te g_te^T,  g = dloss/dy

with running averages, pi-damping, inverses and ``v_e = iG_e g_e iA_e`` per
expert, as for a dense layer (stacks ``[E, ., .]``). The ground is the
layer-wise independence of Martens & Grosse (section 3): an expert is a
layer of its own whose input and output gradient are zero on the rows that
are not routed to it. What it is not: a decay weighted by each expert's
token count, as the program's toy ``KFACMoE`` has; an expert that sees few
rows in a step moves its averages by the same ``1 - stat_decay``.

So that it fits beside a configuration that fills the chip:

* the inverses of one side are iterated in stacks of at most
  :data:`STACK_BYTES` of matrices, one stack after another;
* :class:`Steps` with ``groups`` > 1 takes the tape (inputs and output
  perturbations) for one group of K-FAC layers per forward/backward, the
  loss and the gradients from the first; statistics, running averages,
  inverses and ``v`` are formed a group at a time, ``sum v.g`` accumulated
  across the groups, then the KL clip and SGD as above. The factors and
  inverses of the groups not at work, and the momentum during the first
  half, live on the host (numpy) and are put back a group at a time. With
  one group nothing is moved.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Tape:
    """Records the K-FAC layers' inputs, outputs and (a bank's) routed rows;
    ``only`` (names) keeps it to some of them and passes the others through."""

    def __init__(self, perts=None, only=None):
        self.perts = perts
        self.only = only
        self.inputs = {}
        self.outputs = {}
        self.rows = {}

    def layer(self, name, x, y, rows=None):
        if self.only is not None and name not in self.only:
            return y
        self.inputs[name] = x
        self.outputs[name] = y
        if rows is not None:
            self.rows[name] = rows
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
    """A layer's gradient in the [out, in(+1)] layout (a bank's: one such
    matrix per expert, ``[E, out, in]``)."""
    k = g["kernel"]
    if layer["kind"] == "conv":
        kh, kw, cin, cout = k.shape
        mat = jnp.transpose(k, (3, 2, 0, 1)).reshape(cout, cin * kh * kw)
    else:
        mat = jnp.swapaxes(k, -1, -2)
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
        out["kernel"] = jnp.swapaxes(mat, -1, -2)
    return out


def factor_stats(layer, x, gy, prec, rows=None):
    """(A, G) of one layer from its input ``x`` and output gradient ``gy``
    (of a loss that is a mean over the rows); a bank's from ``rows`` too."""
    if layer["kind"] == "bank":
        n_experts, m = gy.shape[-2:]
        g = jnp.swapaxes(gy.reshape(-1, n_experts, m).astype(jnp.float32), 0, 1)  # [E, T, m]
        r = rows.reshape(-1, n_experts).astype(jnp.float32).T[:, :, None]  # [E, T, 1]
        n = g.shape[1]
        if x.ndim == gy.ndim:  # each expert's own input
            xf = jnp.swapaxes(x.reshape(n, n_experts, -1).astype(jnp.float32), 0, 1)  # [E, T, a]
        else:  # one input, shared
            xf = x.reshape(n, -1).astype(jnp.float32)[None]
        a = _mm(jnp.swapaxes(r * xf, 1, 2), xf, prec) / n
        return a, _mm(jnp.swapaxes(g, 1, 2), g, prec) * n
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


STACK_BYTES = 512 * 2**20  # of float32 matrices in one stack of the iteration, which holds five such arrays


def damped_inverses(facs, damping, eps=1e-10, stack_bytes=STACK_BYTES):
    """``({name: (iA, iG)}, residual)`` from ``{name: (A, G)}`` with the
    pi-corrected factored damping (a bank's factors are stacks ``[E, n, n]``,
    damped and inverted per expert). Matrices of one side are inverted in
    stacks of at most ``stack_bytes``, one stack after another; the residual
    is the largest over the stacks."""
    sqrt_l = jnp.sqrt(jnp.float32(damping))
    mean_diag = lambda m: jnp.trace(m, axis1=-2, axis2=-1) / m.shape[-1]
    jobs = {}  # side -> [(name, 0 for A / 1 for G, damped matrix)], a bank's experts in order
    for name, (a, g) in facs.items():
        pi = jnp.sqrt(jnp.maximum(mean_diag(a), eps) / jnp.maximum(mean_diag(g), eps))
        for which, m, shift in ((0, a, pi * sqrt_l), (1, g, sqrt_l / pi)):
            side = m.shape[-1]
            damped = (m + shift[..., None, None] * jnp.eye(side, dtype=m.dtype)).reshape(-1, side, side)
            jobs.setdefault(side, []).extend((name, which, matrix) for matrix in damped)
    out = {name: ([], []) for name in facs}
    worst = jnp.float32(0.0)
    with jax.default_matmul_precision("highest"):
        for side in sorted(jobs):
            per_stack = max(1, stack_bytes // (4 * side * side))
            for lo in range(0, len(jobs[side]), per_stack):
                stack = jobs[side][lo:lo + per_stack]
                inv, resid = spd_inverse(jnp.stack([m for _, _, m in stack]))
                worst = jnp.maximum(worst, resid)
                for row, (name, which, _) in enumerate(stack):
                    out[name][which].append(inv[row])
    return {name: tuple(jnp.stack(rows).reshape(f.shape) for rows, f in zip(out[name], facs[name]))
            for name in facs}, worst


def factor_sides(layer, kernel_shape):
    """``(shape of A, shape of G)`` of a layer from its kernel's shape."""
    if layer["kind"] == "bank":
        n_experts, a, m = kernel_shape
        return (n_experts, a, a), (n_experts, m, m)
    a = kernel_shape[0] * kernel_shape[1] * kernel_shape[2] if len(kernel_shape) == 4 else kernel_shape[0]
    a += int(layer["bias"])
    return (a, a), (kernel_shape[-1],) * 2


def init_state(model, params, xp=jnp):
    """Factors at the identity, inverses and momentum at zero; ``xp=numpy``
    makes the three on the host."""
    facs, invs = {}, {}
    for layer in model.layers:
        if layer["kind"] == "bank" and layer["bias"]:
            raise ValueError(f"a bank has no bias: {layer['name']}")
        shapes = factor_sides(layer, get_path(params, layer["path"])["kernel"].shape)
        facs[layer["name"]] = tuple(xp.zeros(s, xp.float32) + xp.eye(s[-1], dtype=xp.float32) for s in shapes)
        invs[layer["name"]] = tuple(xp.zeros(s, xp.float32) for s in shapes)
    zeros = jax.tree_util.tree_map(lambda p: xp.zeros(p.shape, p.dtype), params)
    return RefState(params, zeros, facs, invs)


def _loss_grads_stats(model, params, batch, prec, layers, want_grads=True):
    """Loss, gradients (unless ``want_grads`` is false) and the factor
    statistics of ``layers`` (none: no tape) of ``batch``."""
    if not layers:
        loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch, Tape(only=()), prec))(params)
        return loss, grads, None
    only = {layer["name"] for layer in layers}

    def shapes_fn(params):
        tape = Tape(only=only)
        model.loss(params, batch, tape, prec)
        return tape.outputs

    perts = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.float32), jax.eval_shape(shapes_fn, params)
    )

    def loss_fn(params, perts):
        tape = Tape(perts, only)
        return model.loss(params, batch, tape, prec), (tape.inputs, tape.rows)

    (loss, (inputs, rows)), got = jax.value_and_grad(
        loss_fn, argnums=(0, 1) if want_grads else 1, has_aux=True
    )(params, perts)
    grads, gperts = got if want_grads else (None, got)
    stats = {
        layer["name"]: factor_stats(layer, inputs[layer["name"]], gperts[layer["name"]], prec,
                                    rows.get(layer["name"]))
        for layer in layers
    }
    return loss, grads, stats


def loss_grads_stats(model, params, batch, prec, layers, row_blocks, want_grads=True):
    """As above, the batch taken in ``row_blocks`` equal blocks of rows where
    the model's rows do not interact: the loss, the gradients and both
    covariances are means over rows, so they are the means over the blocks."""
    if row_blocks <= 1:
        return _loss_grads_stats(model, params, batch, prec, layers, want_grads)
    if not model.rows_independent:
        raise ValueError("this model's rows interact (batch statistics): no row blocks")
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(row_blocks, a.shape[0] // row_blocks, *a.shape[1:]), batch
    )
    first = jax.tree_util.tree_map(lambda a: a[0], blocks)
    zero = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda p, b: _loss_grads_stats(model, p, b, prec, layers, want_grads), params, first),
    )

    def body(acc, block):
        out = _loss_grads_stats(model, params, block, prec, layers, want_grads)
        return jax.tree_util.tree_map(jnp.add, acc, out), None

    total, _ = lax.scan(body, zero, blocks)
    return jax.tree_util.tree_map(lambda a: a / row_blocks, total)


def forward_backward(model, hyper, state, batch, *, update_factors,
                     prec=Precision(), row_blocks=1, layers=None, want_grads=True):
    """The first half of a step, on the device: ``(loss, clipped gradients,
    factors)``, the factors' running averages moved on where
    ``update_factors``. With ``layers`` (some of the model's) the tape, the
    statistics and the factors returned are of those alone, and
    ``state.factors`` need hold no others; without ``want_grads`` the
    parameters' gradients are not formed (``None``)."""
    layers = model.layers if layers is None else layers
    with jax.default_matmul_precision(prec.matmul_precision):
        loss, grads, stats = loss_grads_stats(
            model, state.params, batch, prec, layers if update_factors else (), row_blocks, want_grads
        )
        if hyper["grad_clip"] and grads is not None:
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
            scale = jnp.minimum(1.0, hyper["grad_clip"] / jnp.maximum(gnorm, 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        facs = {layer["name"]: state.factors[layer["name"]] for layer in layers}
        if update_factors:
            d = hyper["stat_decay"]
            facs = {
                n: (d * facs[n][0] + (1 - d) * stats[n][0], d * facs[n][1] + (1 - d) * stats[n][1])
                for n in facs
            }
    return loss, grads, facs


def precondition(layers, grads, invs, prec=Precision()):
    """``(gradients, sum v.g)``: v = iG g iA in the place of g for each of
    ``layers``, not yet scaled by the KL clip."""
    with jax.default_matmul_precision(prec.matmul_precision):
        vg = jnp.float32(0.0)
        for layer in layers:
            n = layer["name"]
            like = get_path(grads, layer["path"])
            gm = grad_matrix(layer, like)
            v = _mm(_mm(invs[n][1], gm, prec), invs[n][0], prec)
            vg = vg + jnp.sum(v * gm)
            grads = set_path(grads, layer["path"], {**like, **from_grad_matrix(layer, v, like)})
    return grads, vg


def clip_and_update(model, hyper, params, momentum, grads, vg, lr):
    """``(params, momentum, gradients as the optimizer gets them)``: the KL
    clip nu over the K-FAC layers' v, then SGD with weight decay and
    momentum."""
    nu = jnp.minimum(
        1.0, jnp.sqrt(hyper["kl_clip"] / jnp.maximum(jnp.abs(vg * lr**2), 1e-30))
    )
    for layer in model.layers:
        like = get_path(grads, layer["path"])
        grads = set_path(grads, layer["path"], jax.tree_util.tree_map(lambda v: v * nu, like))
    wd, mu = hyper["weight_decay"], hyper["momentum"]
    momentum = jax.tree_util.tree_map(lambda g, p, m: g + wd * p + mu * m, grads, params, momentum)
    params = jax.tree_util.tree_map(lambda p, m: p - lr * m, params, momentum)
    return params, momentum, grads


def precondition_and_update(model, hyper, state, grads, facs, invs, lr, *,
                            prec=Precision()):
    """The second half, on the device: ``(state, gradients as the optimizer
    gets them)``: v = iG g iA per layer, the KL clip, then SGD with weight
    decay and momentum."""
    grads, vg = precondition(model.layers, grads, invs, prec)
    with jax.default_matmul_precision(prec.matmul_precision):
        params, momentum, grads = clip_and_update(model, hyper, state.params, state.momentum, grads, vg, lr)
    return RefState(params, momentum, facs, invs), grads


def hyper_of(cfg):
    """The step's hyperparameters as the functions here take them, from a configuration."""
    return {**cfg["kfac"], "momentum": cfg["momentum"], "weight_decay": cfg["weight_decay"],
            "grad_clip": cfg["grad_clip"]}


def layer_groups(layers, n):
    """``layers`` in ``n`` runs of consecutive layers, as equal as may be."""
    n = max(1, min(n, len(layers)))
    cuts = [round(i * len(layers) / n) for i in range(n + 1)]
    return [layers[lo:hi] for lo, hi in zip(cuts, cuts[1:])]


class Steps:
    """The jitted parts of the reference's step for one model, and the step
    made of them. ``step`` returns ``(state, loss, gradients as the optimizer
    gets them, the inverses' residual or None)``.

    With one group: the first half, the inverses and the second half over
    all layers, everything on the device. With more, a group of layers at a
    time (module docstring): ``state.factors``, ``state.inverses`` and
    ``state.momentum`` are numpy arrays on the host between their uses."""

    def __init__(self, model, hyper, prec=Precision(), row_blocks=1, groups=1, stack_bytes=STACK_BYTES):
        self.model, self.groups = model, layer_groups(model.layers, groups)
        self.inverses = jax.jit(lambda f: damped_inverses(f, hyper["damping"], stack_bytes=stack_bytes))
        first = lambda capture, layers=None, want_grads=True: jax.jit(
            lambda st, b: forward_backward(model, hyper, st, b, update_factors=capture, prec=prec,
                                           row_blocks=row_blocks, layers=layers, want_grads=want_grads))
        if len(self.groups) == 1:
            self.first_half = {capture: first(capture) for capture in (True, False)}
            self.second_half = jax.jit(lambda st, g, f, i, lr: precondition_and_update(
                model, hyper, st, g, f, i, lr, prec=prec))
            return
        self.plain = first(False, layers=())
        self.capture = [first(True, layers=group, want_grads=i == 0) for i, group in enumerate(self.groups)]
        self.precondition = [
            jax.jit(lambda g, i, group=group: precondition(group, g, i, prec), donate_argnums=0)
            for group in self.groups]
        self.finish = jax.jit(
            lambda p, m, g, vg, lr: clip_and_update(model, hyper, p, m, g, vg, lr), donate_argnums=(1, 2))

    def init(self, params):
        return init_state(self.model, params, xp=jnp if len(self.groups) == 1 else np)

    def step(self, state, batch, lr, *, capture, refresh):
        if len(self.groups) > 1:
            return self._step_in_groups(state, batch, lr, capture, refresh)
        loss, grads, facs = self.first_half[capture](state, batch)
        invs, resid = self.inverses(facs) if refresh else (state.inverses, None)
        state, grads = self.second_half(state, grads, facs, invs, lr)
        return state, loss, grads, resid

    def _step_in_groups(self, state, batch, lr, capture, refresh):
        on_device = RefState(state.params, None, None, None)
        facs_host, invs_host = dict(state.factors), dict(state.inverses)
        loss = grads = resid = None
        vg = jnp.float32(0.0)
        if not capture:
            loss, grads, _ = self.plain(on_device, batch)
        for i, group in enumerate(self.groups):
            names = [layer["name"] for layer in group]
            facs = {n: facs_host[n] for n in names}
            if capture:
                got = self.capture[i](on_device._replace(factors=jax.device_put(facs)), batch)
                facs = got[2]
                if i == 0:
                    loss, grads = got[:2]
                del got
            if refresh:
                invs, worst = self.inverses(facs)
                resid = worst if resid is None else jnp.maximum(resid, worst)
            else:
                invs = jax.device_put({n: invs_host[n] for n in names})
            grads, part = self.precondition[i](grads, invs)  # dispatched before the fetches, which it overlaps
            vg = vg + part
            if refresh:
                invs_host.update(jax.device_get(invs))
            if capture:
                facs_host.update(jax.device_get(facs))
            del facs, invs
        params, momentum, grads = self.finish(state.params, jax.device_put(state.momentum), grads, vg, lr)
        return RefState(params, jax.device_get(momentum), facs_host, invs_host), loss, grads, resid
