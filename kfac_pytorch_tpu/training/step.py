"""Jitted train/eval steps: forward + vjp + K-FAC + SGD in one XLA program.

Replaces the reference's per-batch hot loop (pytorch_cifar10_resnet.py:
220-241): where torch needed ``optimizer.synchronize()`` (grad allreduce
barrier) → ``preconditioner.step()`` (factor/eigen allreduces) →
``optimizer.step()`` as three separately-synchronized phases, here the whole
thing is ONE compiled SPMD program per step variant — the batch is sharded
over the mesh's data axis, so XLA inserts and overlaps every collective
(grad mean, factor mean, eigendecomp exchange) automatically.

Step variants are selected HOST-side from the step counter and the K-FAC
update frequencies (the ``steps % freq`` gates of kfac_preconditioner.py:
369-399 are host-known), so plain steps trace no capture/eigh code at all.
Each (update_factors, update_eigen) combination compiles once and is cached.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax import lax

from kfac_pytorch_tpu import capture, compat
from kfac_pytorch_tpu.models.layers import KFAC_ACTS, KFAC_TAPE, PERTURBATIONS, STEP_SCALARS
from kfac_pytorch_tpu.observability.diagnostics import diagnostic_metrics
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.observability.telemetry import get_telemetry
from kfac_pytorch_tpu.ops import factor_kernels, factors, flash_attention
from kfac_pytorch_tpu.ops import precondition as precond_ops
from kfac_pytorch_tpu.preconditioner import KFAC

PyTree = Any


def require_pure_dp_mesh(mesh):
    """The compressed-grad wrappers need every device to see whole examples:
    returns the batch axis name(s), rejecting meshes with a real second axis.

    Axes named ``tensor*`` are exempt (parallel/mesh.py::data_tensor_mesh):
    by convention they are replicated-compute — parameters and batch carry
    ``P()`` over them, so every tensor replica still sees whole examples and
    all K-FAC/grad collectives stay confined to the data axis. Axes named
    ``fsdp*`` (parallel/mesh.py::data_fsdp_tensor_mesh) are batch-CARRYING:
    parameters shard their leading dim over them but the batch shards too,
    so each device still sees whole examples — they join the returned
    reduction axis, which is then a TUPLE ``('data', 'fsdp')`` (both
    ``PartitionSpec`` dim entries and ``lax.pmean``/``psum`` axis arguments
    accept tuples transparently). Pure-DP meshes keep returning the plain
    string so existing single-axis callers are untouched.
    """
    bad = [
        a
        for a in mesh.axis_names[1:]
        if mesh.shape[a] > 1
        and not (str(a).startswith("tensor") or str(a).startswith("fsdp"))
    ]
    if bad:
        raise ValueError(
            "grad_comm_dtype requires a data-plane mesh (non-data axes of "
            f"size 1 or named 'tensor*'/'fsdp*'); got {dict(mesh.shape)} — a "
            "sequence/model axis would make the per-device local forward "
            "see a partial example"
        )
    fsdp = tuple(
        str(a)
        for a in mesh.axis_names[1:]
        if str(a).startswith("fsdp") and mesh.shape[a] > 1
    )
    if fsdp:
        return (mesh.axis_names[0],) + fsdp
    return mesh.axis_names[0]


def pmean_compressed(tree: PyTree, axis: str, comm_dtype) -> PyTree:
    """Cross-device mean with the wire payload downcast to ``comm_dtype``
    (each device's partial value rounds once; the mean itself is exact in
    the psum's accumulation) and the result restored to f32."""
    return jax.tree_util.tree_map(
        lambda g: lax.pmean(g.astype(comm_dtype), axis).astype(jnp.float32),
        tree,
    )


def _compressed_grads(compute, mesh, comm_dtype, accum_steps, factor_comm=None):
    """Wrap a loss-and-grads computation so the DP gradient mean crosses the
    wire in ``comm_dtype`` — the reference's ``--fp16-allreduce`` Horovod
    compression (pytorch_cifar10_resnet.py:190-195), TPU-native.

    Under plain GSPMD the grad reduction is implicit (XLA inserts an f32
    psum over the sharded batch axis), so there is no tensor to cast. This
    wrapper makes the reduction explicit: a ``shard_map`` over the (single)
    mesh axis computes per-device grads from the LOCAL microbatch, casts
    them to ``comm_dtype``, and one ``pmean`` reassembles — only the
    downcast values travel. Exact up to the downcast rounding of each
    device's partial gradient.

    K-FAC factor statistics exchange alongside through ``factor_comm`` (the
    preconditioner's ``FactorComm`` plane, parallel/comm.py): all per-layer
    A/G leaves fuse into a few flat buckets — one collective per bucket
    instead of two per layer — optionally downcast for the wire, or (in
    deferred mode) not reduced here at all; at f32/freq-1 defaults the
    bucketed mean is bitwise what the old per-layer pmeans produced. With
    ``factor_comm=None`` (no preconditioner) there are no statistics.

    Semantics note, same as the reference: BatchNorm inside the wrapper
    normalizes over the LOCAL per-device batch (each Horovod rank's torch BN
    sees only its own batch too), where the GSPMD path's global-batch mean
    acts like sync-BN; running stats are pmean'd so state stays replicated.
    """
    from functools import partial

    from jax.sharding import PartitionSpec as P

    axis = require_pure_dp_mesh(mesh)
    bspec = P(None, axis) if accum_steps > 1 else P(axis)

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), bspec, bspec),
        out_specs=P(),
        check_vma=False,
    )
    def _inner(params, batch_stats, images, labels):
        loss, acc, grads, new_bs, a_c, g_s, scalars, _ = compute(
            params, batch_stats, images, labels
        )
        overlap = factor_comm is not None and factor_comm.overlap
        if overlap and a_c is not None:
            # Overlap plane, mechanism (a): issue the factor-bucket
            # reductions BEFORE the gradient pmean so the two collective
            # streams interleave — factor statistics cross the wire while
            # the (larger) gradient reduction is still draining, instead of
            # queuing behind it. Every reduction is an independent mean, so
            # the values are bitwise those of the serial order below.
            a_c, g_s = factor_comm.exchange_contribs(a_c, g_s, axis)
        grads = pmean_compressed(grads, axis, comm_dtype)
        loss, acc = lax.pmean(loss, axis), lax.pmean(acc, axis)
        if new_bs:
            new_bs = lax.pmean(new_bs, axis)
        if a_c is not None and not overlap:
            if factor_comm is not None:
                a_c, g_s = factor_comm.exchange_contribs(a_c, g_s, axis)
            else:
                # standalone use without a preconditioner plane: keep the
                # per-leaf f32 exchange
                a_c = lax.pmean(a_c, axis)
                g_s = lax.pmean(g_s, axis)
        # no bank tape: a device's rows make its own gradient, not the mean
        return loss, acc, grads, new_bs, a_c, g_s, lax.pmean(scalars, axis), None

    return _inner


@flax.struct.dataclass
class TrainState:
    """Full training state pytree (checkpointable, incl. K-FAC curvature)."""

    step: jnp.ndarray
    params: PyTree
    batch_stats: PyTree
    opt_state: PyTree
    kfac_state: Optional[PyTree] = None


def make_bn_recal_step(model, train_kwargs: Optional[dict] = None):
    """Jitted BatchNorm-statistics refresh: one train-mode forward that
    updates ONLY ``batch_stats`` (no grads, no param change).

    Why: at high lr the last optimizer steps of an epoch move the network
    faster than the BN running EMAs (momentum 0.9 ≈ a ~10-batch window)
    can track, so eval — which normalizes with those stale stats — reports
    transient accuracy dips while train-mode accuracy (batch statistics)
    is unaffected. Observed on both K-FAC and SGD runs at peak lr
    (logs/cifar10_resnet32_*_r4; the K-FAC diagnostics show ν and the
    damped spectrum healthy through the dips, ruling out the
    preconditioner). A few recalibration forwards before eval re-center
    the EMAs on the CURRENT weights; 0.9^30 ≈ 0.04 residual history.
    """
    kwargs = dict(train_kwargs or {"train": True})

    def recal(state: "TrainState", images: jnp.ndarray) -> "TrainState":
        _, mut = model.apply(
            _variables(state.params, state.batch_stats),
            images,
            mutable=["batch_stats"],
            **kwargs,
        )
        return state.replace(batch_stats=mut["batch_stats"])

    return jax.jit(recal, donate_argnames=("state",))


def make_sgd(momentum: float = 0.9, weight_decay: float = 0.0):
    """SGD pieces matching ``torch.optim.SGD`` semantics.

    Weight decay is added to the (preconditioned) gradient, then momentum,
    then the lr scaling — the exact order torch applies when K-FAC has
    rewritten ``param.grad`` (SURVEY.md §1 integration contract). lr stays a
    traced scalar (applied by the train step), so schedulers never recompile.
    """
    chain = []
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay))
    chain.append(optax.trace(decay=momentum, nesterov=False))
    return optax.chain(*chain)


# Trace-time count of the closed-form losses built since the last
# :func:`reset_loss_tally` (the precedent is ops/factors.py::_TALLY).
_TALLY = {"calls": 0}


def reset_loss_tally() -> None:
    """Start the count behind ``loss/closed_form_calls`` anew. The step
    builders call it where a step program's forward/backward starts to trace,
    so the gauge describes that program."""
    _TALLY["calls"] = 0


def _class_iota(x):
    return lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)


def _cross_entropy_fwd(logits, labels, label_smoothing):
    x = logits.astype(jnp.float32)
    v = x.shape[-1]
    m = jnp.max(x, axis=-1, keepdims=True)
    # ONE pass over the classes for the sum of exponentials, the first index
    # of the maximum (jnp.argmax's answer; V where the row holds a NaN), the
    # label's logit and, under smoothing, the sum of the logits. The label's
    # logit is a member of the reduce and not a gather: on the v5e a gather
    # out of the logits moved the whole step program's activations out of the
    # chip's fast memory and cost what the closed form saves (PERF.md, PR 31).
    # Every term is taken relative to the maximum, so nothing is rounded at
    # the logits' magnitude.
    iota = _class_iota(x)
    operands = [
        jnp.exp(x - m),
        jnp.where(x == m, iota, v),
        jnp.where(iota == labels[..., None], x - m, 0.0),
    ]
    inits = [jnp.float32(0.0), jnp.int32(v), jnp.float32(0.0)]
    combiners = [jnp.add, jnp.minimum, jnp.add]
    if label_smoothing > 0.0:
        operands.append(x - m)
        inits.append(jnp.float32(0.0))
        combiners.append(jnp.add)
    l, idx, picked, *total = lax.reduce(
        operands,
        inits,
        lambda a, b: tuple(f(p, q) for f, p, q in zip(combiners, a, b)),
        (x.ndim - 1,),
    )
    log_l = jnp.log(l)
    loss = log_l - (1.0 - label_smoothing) * picked
    if label_smoothing > 0.0:
        loss = loss - label_smoothing * total[0] / v
    correct = (idx == labels).astype(jnp.float32)
    return (loss, correct), (logits, m, log_l, labels)


def _cross_entropy_bwd(label_smoothing, residuals, cotangents):
    logits, m, log_l, labels = residuals
    g, _ = cotangents  # nothing is differentiated through the accuracy
    x = logits.astype(jnp.float32)
    target = (_class_iota(x) == labels[..., None]).astype(jnp.float32)
    if label_smoothing > 0.0:
        target = (1.0 - label_smoothing) * target + label_smoothing / x.shape[-1]
    dx = (jnp.exp(x - m - log_l[..., None]) - target) * g[..., None]
    return dx.astype(logits.dtype), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _cross_entropy(logits, labels, label_smoothing):
    return _cross_entropy_fwd(logits, labels, label_smoothing)[0]


_cross_entropy.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)


def per_sample_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, label_smoothing: float = 0.0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-sample CE with optional label smoothing, and whether the first
    maximal logit is the label's (float32 0/1) → two arrays of the labels'
    shape, from one reduction over the classes.

    Closed form, forward and backward (``lse - logits[label]``; ``softmax -
    target``), float32 throughout, over the LAST axis of the logits as they
    come: an LM head writes ``[B, T, V]`` logits classes-second-minor on the
    TPU, and flattening them first costs a copy of the whole array. An
    out-of-range label picks no logit (its loss is the row's ``lse - max``).
    """
    _TALLY["calls"] += 1
    get_telemetry().set_gauge("loss/closed_form_calls", _TALLY["calls"])
    return _cross_entropy(logits, labels, float(label_smoothing))


def cross_entropy_and_accuracy(
    logits: jnp.ndarray, labels: jnp.ndarray, label_smoothing: float = 0.0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean CE and top-1 accuracy over every row, reading the logits once."""
    loss, correct = per_sample_cross_entropy(logits, labels, label_smoothing)
    return jnp.mean(loss), jnp.mean(correct)


def softmax_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, label_smoothing: float = 0.0
) -> jnp.ndarray:
    """Mean CE with optional label smoothing (examples/utils.py:19-31)."""
    return cross_entropy_and_accuracy(logits, labels, label_smoothing)[0]


def _variables(params, batch_stats, extra=None):
    v = {"params": params}
    if batch_stats:
        v["batch_stats"] = batch_stats
    if extra:
        v.update(extra)
    return v


def clip_scale(grads: PyTree, max_norm: float) -> jnp.ndarray:
    """The one factor by which :func:`clip_by_global_norm` scales every leaf."""
    gnorm = optax.global_norm(grads)
    return jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))


def clip_by_global_norm(grads: PyTree, max_norm: float) -> PyTree:
    """``torch.nn.utils.clip_grad_norm_`` semantics (scale if above max)."""
    scale = clip_scale(grads, max_norm)
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    kfac: Optional[KFAC] = None,
    label_smoothing: float = 0.0,
    train_kwargs: Optional[dict] = None,
    accum_steps: int = 1,
    grad_clip: float = 0.0,
    stats_all_microbatches: bool = False,
    mesh=None,
    grad_comm_dtype=None,
    sgd_hyper: Optional[Tuple[float, float]] = None,
):
    """Build the jitted train step.

    ``grad_comm_dtype`` (e.g. ``jnp.bfloat16``, requires ``mesh``) compresses
    the data-parallel gradient mean on the wire — see
    :func:`_compressed_grads`. ``None`` (default) leaves the reduction to
    GSPMD at f32.

    ``sgd_hyper`` is accepted and unused: the optimizer pass is always
    ``tx.update``. It stays in the signature only because
    ``benchmarks/configs/transformer_lm.py`` passes it and a PR that is not
    a ``benchmark`` PR may not edit that file (ROADMAP.md, Queue C).

    ``KFAC(factor_sharding="owner")`` needs NO step-level wiring: it makes
    ``kfac.factor_comm.active`` true, which routes the step through the
    same :func:`_compressed_grads` wrapper (grads pmean at f32 unless
    compressed), ``exchange_contribs`` hands the preconditioner LOCAL
    statistics, and ``KFAC.update`` itself issues the reduce-scatter /
    all-gather pair. The flag surface (and so ``expected_step_variants``)
    is identical in both sharding modes.

    Returns ``step_fn(state, batch, lr, damping, update_factors=...,
    update_eigen=...)`` → ``(state, metrics)``. ``lr``/``damping`` are traced
    scalars; the two flags are static (compile-cached per combination).
    With ``kfac=None`` this is the plain-SGD baseline path (the reference's
    ``--kfac-update-freq 0`` mode, pytorch_cifar10_resnet.py:169).

    ``accum_steps > 1`` is gradient accumulation (the reference's
    ``--batches-per-allreduce`` sub-batch loop, pytorch_cifar10_resnet.py:
    225-235): the batch arrives with a leading ``[accum_steps, ...]``
    microbatch axis (sharded ``P(None, 'data')``), grads are averaged over a
    ``lax.scan`` of microbatches. K-FAC statistics default to the LAST
    microbatch only — the structural analog of the reference, whose hooks
    overwrite ``m_a``/``m_g`` every sub-batch forward. Two deliberate
    divergences from the reference under accumulation:

    * The reference pre-divides each sub-batch loss by the accumulation
      count before ``backward()`` (pytorch_cifar10_resnet.py:230-234), so
      its hooked grad-outputs — and hence G — shrink by ``accum_steps²``.
      Here statistics come from the UNSCALED microbatch loss, keeping the
      G/damping balance identical to the ``accum_steps == 1`` run: the
      curvature estimate should not depend on how the batch was split.
    * ``stats_all_microbatches=True`` captures statistics on EVERY
      microbatch and averages them, which equals computing them on the full
      effective batch at once (each microbatch stat is an unbiased
      per-sample average) — strictly better statistics at the cost of
      running the capture path in the scan body.
    """
    train_kwargs = dict(train_kwargs or {})
    if grad_comm_dtype is not None and mesh is None:
        raise ValueError(
            "grad_comm_dtype compresses the data-parallel gradient mean and "
            "needs mesh= to know the reduction axis — refusing a config "
            "whose numerics would silently change when run at scale"
        )
    # Factor-communication plane (parallel/comm.py). When its knobs are
    # non-default the factor exchange must be an EXPLICIT collective, so the
    # step routes through the shard_map wrapper even without grad_comm_dtype
    # (grads then pmean at f32); the plane was validated against kfac.mesh,
    # which becomes the wrapper mesh unless the caller passed one.
    factor_comm = kfac.factor_comm if kfac is not None else None
    comm_active = factor_comm is not None and factor_comm.active
    if comm_active and mesh is None:
        mesh = kfac.mesh
    # the expert banks whose tape a step that captures nothing keeps too, so
    # that every step may precondition them from their rows (none in a model
    # without banks, whose plain program is what it was)
    tape_banks = [
        n for n in (kfac.layers or [] if kfac is not None else [])
        if capture.split_bank_name(n)[1] is not None
    ]

    def loss_and_grads_captured(params, batch_stats, images, labels):
        # Trace-time scope: the KFACConv layers inside model.apply route
        # their A contributions through the configured factor kernel
        # (ops/factor_kernels.py) — "pallas" skips the im2col temporary.
        with factor_kernels.factor_kernel_scope(
            kfac.factor_kernel if kfac is not None else "dense"
        ):
            return _loss_and_grads_captured(params, batch_stats, images, labels)

    def _loss_and_grads_captured(params, batch_stats, images, labels):
        perts = capture.perturbation_zeros(model, images, **train_kwargs)
        factors.reset_capture_tally()  # the gauges count this program's products
        flash_attention.reset_flash_tally()  # and its attention kernels
        reset_loss_tally()  # and its losses
        precond_ops.reset_tallies()  # and its banks preconditioned by rows, its refresh
        has_bn = bool(batch_stats)
        mutable = (["batch_stats"] if has_bn else []) + [KFAC_ACTS, KFAC_TAPE, STEP_SCALARS]

        def loss_fn(params, perts):
            out = model.apply(
                _variables(params, batch_stats, {PERTURBATIONS: perts}),
                images,
                mutable=mutable,
                **train_kwargs,
            )
            logits, mut = out
            loss, acc = cross_entropy_and_accuracy(logits, labels, label_smoothing)
            return loss, (mut, acc)

        with phase("model"):
            (loss, (mut, acc)), (grads, gperts) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(params, perts)
        if kfac is not None and kfac.layers is not None:
            names = kfac.layers
        else:
            names = capture.layer_names_from_capture(mut[KFAC_ACTS])
        ba = kfac.batch_averaged if kfac else True
        # cross-args thread the tied-weight (reduce-lens) statistics: the
        # decoder-site contributions live on the perturbation-grad side for A
        # and the captured side for G (capture.py, arxiv 2311.00636)
        a_c = capture.a_contribs(
            mut[KFAC_ACTS], names, perturb_grads=gperts, batch_averaged=ba
        )
        g_s = capture.g_factors(
            gperts, names, batch_averaged=ba, captured=mut[KFAC_ACTS]
        )
        # an expert bank's routed rows and their cotangents: its gradient
        # is their product, so the apply can work from them
        tape = capture.bank_tape(
            mut.get(KFAC_TAPE, {}), gperts, names, kfac.shared_a if kfac else {}
        )
        new_bs = mut.get("batch_stats", batch_stats)
        return loss, acc, grads, new_bs, a_c, g_s, _step_scalars(mut), tape

    def _step_scalars(mut):
        # scalars the model reports beside the loss (models/layers.py::
        # STEP_SCALARS: a sparse-expert model's routing load), for the metrics
        return {
            name: value[-1] if isinstance(value, tuple) else value
            for name, value in mut.get(STEP_SCALARS, {}).items()
        }

    def loss_and_grads_plain(params, batch_stats, images, labels, taped=False):
        flash_attention.reset_flash_tally()  # the gauges count this program's kernels
        reset_loss_tally()  # and its losses
        precond_ops.reset_tallies()  # and its banks preconditioned by rows, its refresh
        has_bn = bool(batch_stats)
        mutable = (["batch_stats"] if has_bn else []) + [STEP_SCALARS]
        # ``taped``: the banks' tape (capture.bank_tape), read through
        # perturbations of their outputs alone; nothing else is captured
        perts = (
            capture.bank_perturbation_zeros(model, tape_banks, images, **train_kwargs)
            if taped and tape_banks else {}
        )
        if perts:
            mutable.append(KFAC_TAPE)

        def loss_fn(params, perts):
            logits, mut = model.apply(
                _variables(params, batch_stats, {PERTURBATIONS: perts} if perts else None),
                images,
                mutable=mutable,
                **train_kwargs,
            )
            loss, acc = cross_entropy_and_accuracy(logits, labels, label_smoothing)
            return loss, (mut, acc)

        tape = None
        with phase("model"):
            if perts:
                (loss, (mut, acc)), (grads, gperts) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True
                )(params, perts)
                tape = capture.bank_tape(mut[KFAC_TAPE], gperts, tape_banks, kfac.shared_a)
            else:
                (loss, (mut, acc)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, perts)
        new_bs = mut.get("batch_stats", batch_stats)
        return loss, acc, grads, new_bs, None, None, _step_scalars(mut), tape

    @phase("model")
    def accum_loss_and_grads(params, batch_stats, images, labels, capture_stats):
        # images/labels: [accum_steps, microbatch, ...]; BN stats thread
        # sequentially through microbatches like the reference's sub-batch
        # forwards; the tail microbatch runs the capture path when needed.
        head = accum_steps - 1 if capture_stats else accum_steps

        def body(carry, xs):
            bs, gsum, lsum, asum = carry
            im, lb = xs
            loss, acc, grads, new_bs, *_ = loss_and_grads_plain(
                params, bs, im, lb
            )
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            return (new_bs, gsum, lsum + loss, asum + acc), None

        carry = (
            batch_stats,
            jax.tree_util.tree_map(jnp.zeros_like, params),
            jnp.float32(0.0),
            jnp.float32(0.0),
        )
        (bs, gsum, lsum, asum), _ = lax.scan(
            body, carry, (images[:head], labels[:head])
        )
        a_c = g_s = None
        if capture_stats:
            loss, acc, grads, bs, a_c, g_s, *_ = loss_and_grads_captured(
                params, bs, images[-1], labels[-1]
            )
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            lsum, asum = lsum + loss, asum + acc
        inv = 1.0 / accum_steps
        grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
        # no bank tape: one microbatch's rows do not make the summed gradient
        return lsum * inv, asum * inv, grads, bs, a_c, g_s, {}, None

    @phase("model")
    def accum_loss_and_grads_all_stats(params, batch_stats, images, labels):
        # stats_all_microbatches path: capture runs in EVERY scan iteration
        # and the per-microbatch factor statistics are averaged (== the
        # full-effective-batch statistics; see make_train_step docstring).
        stat_shapes = jax.eval_shape(
            loss_and_grads_captured,
            params, batch_stats, images[0], labels[0],
        )
        zeros_like_shape = lambda tree: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), tree
        )

        def body(carry, xs):
            bs, gsum, lsum, asum, a_sum, g_sum = carry
            im, lb = xs
            loss, acc, grads, new_bs, a_c, g_s, *_ = loss_and_grads_captured(
                params, bs, im, lb
            )
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            with phase("kfac_capture"):
                a_sum = jax.tree_util.tree_map(jnp.add, a_sum, a_c)
                g_sum = jax.tree_util.tree_map(jnp.add, g_sum, g_s)
            return (new_bs, gsum, lsum + loss, asum + acc, a_sum, g_sum), None

        carry = (
            batch_stats,
            jax.tree_util.tree_map(jnp.zeros_like, params),
            jnp.float32(0.0),
            jnp.float32(0.0),
            zeros_like_shape(stat_shapes[4]),
            zeros_like_shape(stat_shapes[5]),
        )
        (bs, gsum, lsum, asum, a_sum, g_sum), _ = lax.scan(
            body, carry, (images, labels)
        )
        inv = 1.0 / accum_steps
        grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
        with phase("kfac_capture"):
            a_c = jax.tree_util.tree_map(lambda a: a * inv, a_sum)
            g_s = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
        return lsum * inv, asum * inv, grads, bs, a_c, g_s, {}, None

    def train_step(
        state: TrainState,
        batch: Tuple[jnp.ndarray, jnp.ndarray],
        lr: jnp.ndarray,
        damping: jnp.ndarray,
        *,
        update_factors: bool = False,
        update_eigen: bool = False,
        diag_warmup_done: bool = True,
        eigen_chunk=None,
        swap_eigen: bool = False,
        flush_factors: bool = False,
    ):
        images, labels = batch
        capture_stats = kfac is not None and update_factors

        def _compute(params, batch_stats, images, labels):
            if accum_steps > 1 and capture_stats and stats_all_microbatches:
                return accum_loss_and_grads_all_stats(
                    params, batch_stats, images, labels
                )
            elif accum_steps > 1:
                return accum_loss_and_grads(
                    params, batch_stats, images, labels, capture_stats
                )
            elif capture_stats:
                return loss_and_grads_captured(
                    params, batch_stats, images, labels
                )
            return loss_and_grads_plain(params, batch_stats, images, labels, taped=True)

        use_wrapper = (
            (grad_comm_dtype is not None or comm_active)
            and mesh is not None
            and mesh.devices.size > 1
        )
        if use_wrapper:
            loss, acc, grads, new_bs, a_c, g_s, scalars, tape = _compressed_grads(
                _compute,
                mesh,
                grad_comm_dtype if grad_comm_dtype is not None else jnp.float32,
                accum_steps,
                factor_comm,
            )(state.params, state.batch_stats, images, labels)
        else:
            loss, acc, grads, new_bs, a_c, g_s, scalars, tape = _compute(
                state.params, state.batch_stats, images, labels
            )

        grad_scale = None
        if grad_clip:
            # between grad averaging and preconditioning, the reference's
            # clip point (pytorch_wikitext_rnn.py:297-300)
            with phase("grad_clip"):
                grad_scale = clip_scale(grads, grad_clip)
                grads = jax.tree_util.tree_map(lambda g: g * grad_scale, grads)

        kfac_state = state.kfac_state
        if kfac is not None:
            grads, kfac_state = kfac.update(
                grads,
                kfac_state,
                a_contribs=a_c,
                g_factor_stats=g_s,
                bank_tape=tape,
                grad_scale=grad_scale,
                lr=lr,
                damping=damping,
                update_factors=update_factors,
                update_eigen=update_eigen,
                diag_warmup_done=diag_warmup_done,
                eigen_chunk=eigen_chunk,
                swap_eigen=swap_eigen,
                flush_factors=flush_factors,
            )

        with phase("optimizer"):
            updates, opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            updates = jax.tree_util.tree_map(lambda u: -lr * u, updates)
            params = optax.apply_updates(state.params, updates)

        metrics = {"loss": loss, "accuracy": acc}
        metrics.update(scalars)
        if kfac is not None and kfac.track_diagnostics:
            metrics.update(diagnostic_metrics(kfac_state["diagnostics"]))
        if kfac_state is not None and "spectrum_mass" in kfac_state:
            # randomized solver only: fraction of factor trace the truncated
            # eigenbases captured at the last refresh (→ the trainer's
            # kfac/spectrum_mass_captured gauge)
            metrics["kfac_spectrum_mass"] = kfac_state["spectrum_mass"]
        if kfac_state is not None and "stream_residual" in kfac_state:
            # streaming solver: curvature mass fraction outside the retained
            # bases after the last fold — the value the trainer hands back to
            # the cadence via kfac.stream_drift_signal
            metrics["kfac_stream_residual"] = kfac_state["stream_residual"]
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            batch_stats=new_bs,
            opt_state=opt_state,
            kfac_state=kfac_state,
        )
        return new_state, metrics

    return jax.jit(
        train_step,
        static_argnames=(
            "update_factors",
            "update_eigen",
            "diag_warmup_done",
            "eigen_chunk",
            "swap_eigen",
            "flush_factors",
        ),
        donate_argnames=("state",),
    )


def make_eval_step(model, label_smoothing: float = 0.0, eval_kwargs: Optional[dict] = None):
    """Jitted eval step → ``{'loss', 'accuracy'}`` means over the batch."""
    eval_kwargs = dict(eval_kwargs or {})

    def eval_step(state: TrainState, batch):
        images, labels = batch
        logits = model.apply(
            _variables(state.params, state.batch_stats), images, **eval_kwargs
        )
        loss, acc = cross_entropy_and_accuracy(logits, labels, label_smoothing)
        return {"loss": loss, "accuracy": acc}

    return jax.jit(eval_step)


def make_masked_eval_step(
    model, label_smoothing: float = 0.0, eval_kwargs: Optional[dict] = None
):
    """Jitted masked eval step for full-split evaluation.

    Takes ``(images, labels, mask)`` batches (see ``data.eval_batches``) and
    returns GLOBAL sums ``{'loss_sum', 'correct', 'count'}`` — padded tail
    samples carry ``mask == 0`` and contribute nothing, so accumulating these
    sums over an epoch and dividing by ``count`` evaluates the entire split
    (the reference evaluates the full val set; the drop-last train iterator
    must not be reused for eval).
    """
    eval_kwargs = dict(eval_kwargs or {})

    def eval_step(state: TrainState, batch):
        images, labels, mask = batch
        logits = model.apply(
            _variables(state.params, state.batch_stats), images, **eval_kwargs
        )
        ce, correct = per_sample_cross_entropy(logits, labels, label_smoothing)
        return {
            "loss_sum": jnp.sum(ce * mask),
            "correct": jnp.sum(correct * mask),
            "count": jnp.sum(mask),
        }

    return jax.jit(eval_step)


def kfac_flags_for_step(
    step: int, kfac: Optional[KFAC], epoch: Optional[int] = None
) -> dict:
    """Host-side step gating (kfac_preconditioner.py:369,383).

    Derives the static flags from the host-known step counter, the
    (scheduler-mutable) update frequencies, and — for the ``diag_warmup``
    gate (kfac_preconditioner.py:361-367) — the current epoch (None → no
    warmup gating, matching the reference's warning path).

    For ``solver="streaming"`` this helper is the degenerate cadence:
    ``update_eigen`` fires at every ``kfac_update_freq`` boundary, i.e.
    re-orthonormalize unconditionally. Drift-gated re-orth skipping needs
    the stateful ``scheduler.EigenRefreshCadence`` with a wired
    ``kfac.stream_drift_signal``.

    Under the curvature service (``service_devices > 0``) ``update_eigen``
    never fires — the refresh runs on the carved workers and
    ``service.ServiceClient`` installs published bases between steps; only
    capture flags (and boundary-forced deferred flushes, so the published
    snapshot is globally merged) remain.
    """
    if kfac is None:
        return {"update_factors": False, "update_eigen": False}
    hp = kfac.hparams
    service = int(getattr(kfac, "service_devices", 0) or 0) > 0
    boundary = step % hp.kfac_update_freq == 0
    flags = {
        "update_factors": step % hp.fac_update_freq == 0,
        "update_eigen": boundary and not service,
        "diag_warmup_done": epoch is None or epoch >= kfac.diag_warmup,
    }
    comm = getattr(kfac, "factor_comm", None)
    if comm is not None and comm.defer:
        # Deferred factor communication: merge the per-replica running
        # averages every comm_freq-th CAPTURE step, and always on an eigen
        # refresh (which must never read unmerged local factors) or — in
        # service mode — at every boundary whose post-step factor snapshot
        # gets published to the workers. Key only present in deferred
        # mode, so other configs' flag dicts (and compiled-variant sets)
        # are untouched.
        flags["flush_factors"] = (
            flags["update_eigen"]
            or (service and boundary)
            or (
                flags["update_factors"]
                and (step // hp.fac_update_freq) % comm.comm_freq == 0
            )
        )
    return flags
