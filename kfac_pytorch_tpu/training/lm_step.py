"""Language-model train step: truncated BPTT + grad clip + K-FAC.

The RNN analog of training/step.py, mirroring the reference WikiText trainer
(pytorch_wikitext_rnn.py): hidden-state repackaging between bptt segments
(:224-229 — realized as ``lax.stop_gradient`` on the incoming carry), global
grad-norm clipping applied BETWEEN grad averaging and preconditioning
(:297-300), and perplexity metrics (:254-260). Unlike the reference — whose
K-FAC path crashes (stale kwargs, SURVEY.md §2.2) — this one actually
preconditions the decoder.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from kfac_pytorch_tpu import capture, compat
from kfac_pytorch_tpu.models.layers import KFAC_ACTS, PERTURBATIONS
from kfac_pytorch_tpu.observability.diagnostics import diagnostic_metrics
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.ops import factor_kernels, factors, flash_attention
from kfac_pytorch_tpu.ops import precondition as precond_ops
from kfac_pytorch_tpu.preconditioner import KFAC
from kfac_pytorch_tpu.training.step import (
    TrainState,
    clip_by_global_norm as _clip_by_global_norm,
    reset_loss_tally,
    softmax_cross_entropy,
)

PyTree = Any


def make_lm_train_step(
    model,
    tx: optax.GradientTransformation,
    kfac: Optional[KFAC] = None,
    grad_clip: float = 0.25,
    mesh=None,
    grad_comm_dtype=None,
):
    """Build the jitted LM train step.

    ``step_fn(state, batch, carry, dropout_rng, lr, damping,
    update_factors=..., update_eigen=...)`` → ``(state, new_carry, metrics)``.
    ``carry`` is the recurrent state threaded across bptt segments.

    ``grad_comm_dtype`` (e.g. ``jnp.bfloat16``, requires ``mesh``): compress
    the data-parallel gradient mean on the wire — the LM twin of
    ``training.step._compressed_grads`` (the reference's ``--fp16-allreduce``,
    pytorch_wikitext_rnn.py's DistributedOptimizer compression). The
    recurrent carry shards over the batch axis (every cell carry leaf is
    batch-leading) and stays per-device; dropout keys fold in the device
    index so masks are iid across the mesh.
    """
    if grad_comm_dtype is not None and mesh is None:
        raise ValueError(
            "grad_comm_dtype compresses the data-parallel gradient mean and "
            "needs mesh= to know the reduction axis"
        )
    # Factor-communication plane, same plumbing as training.step: active
    # knobs force the explicit-collective wrapper (grads then pmean at f32
    # when grad_comm_dtype is unset), defaulting the wrapper mesh to the
    # plane's own.
    factor_comm = kfac.factor_comm if kfac is not None else None
    comm_active = factor_comm is not None and factor_comm.active
    if comm_active and mesh is None:
        mesh = kfac.mesh

    def _compute(params, tokens, targets, carry, dropout_rng, capture_stats):
        rngs = {"dropout": dropout_rng}
        if capture_stats:
            # Trace-time factor-kernel scope, same as training/step.py —
            # any conv layer in an LM stack (e.g. conv frontends) routes its
            # A contribution through the configured kernel.
            with factor_kernels.factor_kernel_scope(kfac.factor_kernel):
                return _compute_captured(params, tokens, targets, carry, rngs)
        flash_attention.reset_flash_tally()  # the gauges count this program's kernels
        reset_loss_tally()  # and its losses
        precond_ops.reset_tallies()  # and its refresh

        def loss_fn(params):
            logits, new_carry = model.apply(
                {"params": params}, tokens, carry=carry, train=True, rngs=rngs
            )
            loss = softmax_cross_entropy(logits, targets)
            return loss, new_carry

        with phase("model"):
            (loss, new_carry), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
        return loss, grads, None, None, new_carry

    def _compute_captured(params, tokens, targets, carry, rngs):
        perts = capture.perturbation_zeros(model, tokens, train=True)
        factors.reset_capture_tally()  # the gauges count this program's products
        flash_attention.reset_flash_tally()  # and its attention kernels
        reset_loss_tally()  # and its losses
        precond_ops.reset_tallies()  # and its refresh

        def loss_fn(params, perts):
            (logits, new_carry), mut = model.apply(
                {"params": params, PERTURBATIONS: perts},
                tokens,
                carry=carry,
                train=True,
                mutable=[KFAC_ACTS],
                rngs=rngs,
            )
            loss = softmax_cross_entropy(logits, targets)
            return loss, (mut, new_carry)

        with phase("model"):
            (loss, (mut, new_carry)), (grads, gperts) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(params, perts)
        names = (
            kfac.layers
            if kfac.layers is not None
            else capture.layer_names_from_capture(mut[KFAC_ACTS])
        )
        # cross-args thread the tied-weight (reduce-lens) statistics: the
        # decoder-site contributions live on the perturbation-grad side for A
        # and the captured side for G (capture.py, arxiv 2311.00636)
        a_c = capture.a_contribs(
            mut[KFAC_ACTS],
            names,
            perturb_grads=gperts,
            batch_averaged=kfac.batch_averaged,
        )
        g_s = capture.g_factors(
            gperts,
            names,
            batch_averaged=kfac.batch_averaged,
            captured=mut[KFAC_ACTS],
        )
        return loss, grads, a_c, g_s, new_carry

    def _compute_compressed(params, tokens, targets, carry, dropout_rng,
                            capture_stats):
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from kfac_pytorch_tpu.training.step import (
            pmean_compressed,
            require_pure_dp_mesh,
        )

        axis = require_pure_dp_mesh(mesh)

        @partial(
            compat.shard_map,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P()),
            out_specs=(P(), P(), P(), P(), P(axis)),
            check_vma=False,
        )
        def _inner(params, tokens, targets, carry, rng):
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            loss, grads, a_c, g_s, new_carry = _compute(
                params, tokens, targets, carry, rng, capture_stats
            )
            overlap = factor_comm is not None and factor_comm.overlap
            if overlap and a_c is not None:
                # overlap plane: factor buckets issue ahead of the gradient
                # pmean so the collective streams interleave — the LM twin
                # of training.step's fused emission order (values bitwise
                # identical; only the schedule changes)
                a_c, g_s = factor_comm.exchange_contribs(a_c, g_s, axis)
            wire = grad_comm_dtype if grad_comm_dtype is not None else jnp.float32
            grads = pmean_compressed(grads, axis, wire)
            loss = jax.lax.pmean(loss, axis)
            if a_c is not None and not overlap:
                # bucketed/compressed/deferred factor exchange — the LM twin
                # of training.step's routing through the comm plane
                if factor_comm is not None:
                    a_c, g_s = factor_comm.exchange_contribs(a_c, g_s, axis)
                else:
                    a_c = jax.lax.pmean(a_c, axis)
                    g_s = jax.lax.pmean(g_s, axis)
            return loss, grads, a_c, g_s, new_carry

        return _inner(params, tokens, targets, carry, dropout_rng)

    def train_step(
        state: TrainState,
        batch: Tuple[jnp.ndarray, jnp.ndarray],
        carry,
        dropout_rng,
        lr,
        damping,
        *,
        update_factors: bool = False,
        update_eigen: bool = False,
        diag_warmup_done: bool = True,
        eigen_chunk=None,
        swap_eigen: bool = False,
        flush_factors: bool = False,
    ):
        tokens, targets = batch  # [B, T] each
        carry = jax.lax.stop_gradient(carry)  # truncate BPTT at segment edge
        capture_stats = kfac is not None and update_factors

        compute = (
            _compute_compressed
            if (grad_comm_dtype is not None or comm_active)
            and mesh is not None
            and mesh.devices.size > 1
            else _compute
        )
        loss, grads, a_c, g_s, new_carry = compute(
            state.params, tokens, targets, carry, dropout_rng, capture_stats
        )

        if grad_clip:
            with phase("grad_clip"):
                grads = _clip_by_global_norm(grads, grad_clip)

        kfac_state = state.kfac_state
        if kfac is not None:
            grads, kfac_state = kfac.update(
                grads,
                kfac_state,
                a_contribs=a_c,
                g_factor_stats=g_s,
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

        metrics = {"loss": loss, "ppl": jnp.exp(loss)}
        if kfac is not None and kfac.track_diagnostics:
            metrics.update(diagnostic_metrics(kfac_state["diagnostics"]))
        if kfac_state is not None and "spectrum_mass" in kfac_state:
            # randomized solver only — see training/step.py
            metrics["kfac_spectrum_mass"] = kfac_state["spectrum_mass"]
        if kfac_state is not None and "stream_residual" in kfac_state:
            # streaming solver drift gauge — see training/step.py
            metrics["kfac_stream_residual"] = kfac_state["stream_residual"]
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            batch_stats=state.batch_stats,
            opt_state=opt_state,
            kfac_state=kfac_state,
        )
        return new_state, new_carry, metrics

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


def make_lm_eval_step(model):
    """Jitted eval: carry-threaded, no dropout → ``{'loss','ppl'}``."""

    def eval_step(state: TrainState, batch, carry):
        tokens, targets = batch
        logits, new_carry = model.apply(
            {"params": state.params}, tokens, carry=carry, train=False
        )
        loss = softmax_cross_entropy(logits, targets)
        return {"loss": loss, "ppl": jnp.exp(loss)}, new_carry

    return jax.jit(eval_step)


def init_carry(model, params, tokens) -> Any:
    """Zero recurrent carry for a batch shape (train-loop epoch start)."""
    logits_carry = jax.eval_shape(
        lambda: model.apply({"params": params}, tokens, train=False)
    )
    _, carry_shapes = logits_carry
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), carry_shapes
    )
