"""training/step.py's closed-form cross-entropy against the plain expression
it replaced (kept here as the reference, float64 on the CPU): per-row loss,
accuracy and the gradient with respect to the logits, the edge cases of the
one reduction (ties, huge logits, NaN rows, class counts no lane width
divides), and the mean through ``jit`` and a two-device row split."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu import compat
from kfac_pytorch_tpu.observability.telemetry import configure, get_telemetry
from kfac_pytorch_tpu.training import step as step_lib
from kfac_pytorch_tpu.training.step import (
    cross_entropy_and_accuracy,
    per_sample_cross_entropy,
    softmax_cross_entropy,
)


def _plain_rows(logits, labels, label_smoothing):
    """The expression the package held until PR 31 (call it under x64)."""
    num_classes = logits.shape[-1]
    logp = jax.nn.log_softmax(logits)
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logits.dtype)
    if label_smoothing > 0.0:
        onehot = (1.0 - label_smoothing) * onehot + label_smoothing / num_classes
    return -jnp.sum(onehot * logp, axis=-1)


def _in_float64(fn, logits, labels, label_smoothing):
    with jax.enable_x64():
        x = jnp.asarray(np.asarray(logits, np.float64))
        return np.asarray(fn(x, jnp.asarray(np.asarray(labels)), label_smoothing))


def plain_cross_entropy(logits, labels, label_smoothing=0.0):
    """Per-row loss of the plain expression, float64."""
    return _in_float64(_plain_rows, logits, labels, label_smoothing)


def plain_grad(logits, labels, label_smoothing=0.0):
    """d(sum of the per-row losses)/d(logits) of the plain expression, float64."""
    total = lambda x, y, s: jax.grad(lambda x: jnp.sum(_plain_rows(x, y, s)))(x)
    return _in_float64(total, logits, labels, label_smoothing)


def _problem(shape, seed=0, scale=3.0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(*shape) * scale, dtype)
    labels = jnp.asarray(rng.randint(0, shape[-1], size=shape[:-1]), jnp.int32)
    return logits, labels


SHAPES = [(16, 10), (4, 6, 37), (3, 5, 130), (2, 3, 4, 17)]
SMOOTHING = [0.0, 0.1]


@pytest.mark.parametrize("label_smoothing", SMOOTHING, ids=["hard", "smoothed"])
@pytest.mark.parametrize("shape", SHAPES, ids=["2d", "3d", "3d_v130", "4d"])
def test_per_row_loss_matches_the_plain_expression(shape, label_smoothing):
    logits, labels = _problem(shape)
    loss, _ = per_sample_cross_entropy(logits, labels, label_smoothing)
    assert loss.shape == labels.shape and loss.dtype == jnp.float32
    np.testing.assert_allclose(
        loss, plain_cross_entropy(logits, labels, label_smoothing), rtol=2e-6, atol=2e-6
    )


@pytest.mark.parametrize("shape", SHAPES, ids=["2d", "3d", "3d_v130", "4d"])
def test_accuracy_is_argmax_equals_label(shape):
    logits, labels = _problem(shape, scale=1.0)
    # a third of the rows get their label's logit raised to the maximum
    raise_it = np.arange(labels.size).reshape(labels.shape) % 3 == 0
    onehot = jax.nn.one_hot(labels, shape[-1]) * raise_it[..., None]
    logits = logits + 10.0 * onehot
    _, correct = per_sample_cross_entropy(logits, labels)
    assert correct.dtype == jnp.float32 and correct.shape == labels.shape
    want = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    np.testing.assert_array_equal(correct, want)
    assert 0.0 < float(jnp.mean(correct)) < 1.0


@pytest.mark.parametrize("label_smoothing", SMOOTHING, ids=["hard", "smoothed"])
@pytest.mark.parametrize("shape", SHAPES, ids=["2d", "3d", "3d_v130", "4d"])
def test_gradient_matches_the_plain_expression(shape, label_smoothing):
    logits, labels = _problem(shape)
    grad = jax.grad(lambda x: jnp.sum(per_sample_cross_entropy(x, labels, label_smoothing)[0]))(logits)
    assert grad.dtype == logits.dtype and grad.shape == logits.shape
    np.testing.assert_allclose(grad, plain_grad(logits, labels, label_smoothing), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("label_smoothing", SMOOTHING, ids=["hard", "smoothed"])
def test_row_cotangents_scale_the_rows(label_smoothing):
    """A weighted sum of the rows (a masked eval, a mean) scales each row's
    gradient by its weight."""
    logits, labels = _problem((4, 6, 37))
    weights = jnp.asarray(np.random.RandomState(1).rand(4, 6), jnp.float32)
    grad = jax.grad(
        lambda x: jnp.sum(weights * per_sample_cross_entropy(x, labels, label_smoothing)[0])
    )(logits)
    want = plain_grad(logits, labels, label_smoothing) * np.asarray(weights)[..., None]
    np.testing.assert_allclose(grad, want, rtol=1e-5, atol=1e-6)


def test_the_accuracy_carries_no_gradient():
    logits, labels = _problem((4, 37))
    grad = jax.grad(lambda x: jnp.sum(per_sample_cross_entropy(x, labels)[1]))(logits)
    np.testing.assert_array_equal(grad, np.zeros_like(logits))


@pytest.mark.parametrize("label_smoothing", SMOOTHING, ids=["hard", "smoothed"])
def test_bfloat16_logits_compute_in_float32(label_smoothing):
    logits, labels = _problem((4, 6, 37), dtype=jnp.bfloat16)
    loss, correct = per_sample_cross_entropy(logits, labels, label_smoothing)
    assert loss.dtype == jnp.float32 and correct.dtype == jnp.float32
    # the reference reads the same bfloat16 values: what is left is float32 rounding
    np.testing.assert_allclose(
        loss, plain_cross_entropy(logits.astype(jnp.float32), labels, label_smoothing), rtol=2e-6, atol=2e-6
    )
    grad = jax.grad(lambda x: softmax_cross_entropy(x, labels, label_smoothing))(logits)
    assert grad.dtype == jnp.bfloat16
    want = plain_grad(logits.astype(jnp.float32), labels, label_smoothing) / labels.size
    np.testing.assert_allclose(grad.astype(jnp.float32), want, rtol=1e-2, atol=1e-4)


@pytest.mark.parametrize("label,others,want", [
    (2, (5,), 1.0),  # tied with a higher index: the label's is the first
    (5, (2,), 0.0),  # tied with a lower index: that one wins, as with argmax
    (3, (1, 6), 0.0),  # both sides
    (0, (1, 2, 3, 4, 5, 6), 1.0),  # every class tied: index 0
], ids=["higher", "lower", "both", "all"])
def test_first_maximal_index_wins_a_tie(label, others, want):
    row = np.linspace(-1.0, 0.5, 7).astype(np.float32)
    row[[label, *others]] = 2.0
    logits, labels = jnp.asarray(row)[None], jnp.asarray([label], jnp.int32)
    loss, correct = per_sample_cross_entropy(logits, labels)
    assert float(correct[0]) == want == float(jnp.argmax(logits, -1)[0] == label)
    np.testing.assert_allclose(loss, plain_cross_entropy(logits, labels), rtol=2e-6)


@pytest.mark.parametrize("label_smoothing", SMOOTHING, ids=["hard", "smoothed"])
@pytest.mark.parametrize("big", [1e4, -1e4], ids=["plus", "minus"])
def test_huge_logits_stay_finite(big, label_smoothing):
    """No ``inf - inf``: the maximum is taken off before the exponential."""
    logits = jnp.asarray([[big, 0.0, -big, 1.0], [big, big, big, big], [0.0, big, 0.0, big]], jnp.float32)
    labels = jnp.asarray([0, 3, 2], jnp.int32)
    loss, correct = per_sample_cross_entropy(logits, labels, label_smoothing)
    grad = jax.grad(lambda x: jnp.sum(per_sample_cross_entropy(x, labels, label_smoothing)[0]))(logits)
    assert np.isfinite(loss).all() and np.isfinite(grad).all()
    np.testing.assert_allclose(loss, plain_cross_entropy(logits, labels, label_smoothing), rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(grad, plain_grad(logits, labels, label_smoothing), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(correct, (jnp.argmax(logits, -1) == labels).astype(jnp.float32))


def test_a_row_with_a_nan_counts_as_wrong():
    logits = jnp.asarray([[0.0, jnp.nan, 1.0], [0.0, 2.0, 1.0]], jnp.float32)
    labels = jnp.asarray([1, 1], jnp.int32)
    loss, correct = per_sample_cross_entropy(logits, labels)
    assert np.isnan(loss[0]) and np.isfinite(loss[1])
    np.testing.assert_array_equal(correct, [0.0, 1.0])


@pytest.mark.parametrize("classes", [10, 127, 129, 1000, 50257 // 16], ids=lambda v: f"v{v}")
def test_class_counts_no_lane_width_divides(classes):
    logits, labels = _problem((2, 5, classes), seed=classes)
    loss, acc = jax.jit(cross_entropy_and_accuracy)(logits, labels)
    np.testing.assert_allclose(loss, plain_cross_entropy(logits, labels).mean(), rtol=2e-6)
    np.testing.assert_allclose(acc, jnp.mean(jnp.argmax(logits, -1) == labels))


def _mean_value_and_grad(label_smoothing):
    return jax.value_and_grad(lambda x, y: softmax_cross_entropy(x, y, label_smoothing))


@pytest.mark.parametrize("label_smoothing", SMOOTHING, ids=["hard", "smoothed"])
@pytest.mark.parametrize("shape", [(16, 10), (4, 6, 37)], ids=["2d", "3d"])
def test_mean_under_jit(shape, label_smoothing):
    logits, labels = _problem(shape)
    loss, grad = jax.jit(_mean_value_and_grad(label_smoothing))(logits, labels)
    np.testing.assert_allclose(loss, plain_cross_entropy(logits, labels, label_smoothing).mean(), rtol=2e-6)
    np.testing.assert_allclose(
        grad, plain_grad(logits, labels, label_smoothing) / labels.size, rtol=1e-5, atol=1e-7
    )


@pytest.mark.parametrize("how", ["gspmd", "shard_map"])
@pytest.mark.parametrize("shape", [(16, 10), (4, 6, 37)], ids=["2d", "3d"])
def test_mean_under_a_two_device_row_split(shape, how):
    """Plain XLA: the rows split over two devices like any reduction's."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    logits, labels = _problem(shape)
    want_loss = plain_cross_entropy(logits, labels, 0.1).mean()
    want_grad = plain_grad(logits, labels, 0.1) / labels.size
    rows = NamedSharding(mesh, P("data"))
    if how == "gspmd":
        fn = jax.jit(_mean_value_and_grad(0.1), in_shardings=(rows, rows))
        loss, grad = fn(logits, labels)
        assert grad.sharding.is_equivalent_to(rows, grad.ndim)
    else:
        def local(x, y):
            loss, grad = _mean_value_and_grad(0.1)(x, y)
            return jax.lax.pmean(loss, "data"), grad / 2  # each device's mean is over half the rows

        fn = jax.jit(compat.shard_map(
            local, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P(), P("data")), check_vma=False
        ))
        loss, grad = fn(jax.device_put(logits, rows), jax.device_put(labels, rows))
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5, atol=1e-7)


def test_one_reduction_over_the_classes_in_the_traced_forward():
    """The jaxpr of the forward pass holds the row maximum and ONE variadic
    reduce (sum of exponentials, first maximal index); the backward none."""
    logits, labels = _problem((4, 6, 37))

    def reductions(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("reduce") or eqn.primitive.name.startswith("arg"):
                found.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                reductions(sub, found)
        return found

    fwd = jax.make_jaxpr(lambda x: per_sample_cross_entropy(x, labels))(logits)
    assert sorted(reductions(fwd.jaxpr, [])) == ["reduce", "reduce_max"]
    _, vjp = jax.vjp(lambda x: per_sample_cross_entropy(x, labels)[0], logits)
    bwd = jax.make_jaxpr(vjp)(jnp.ones(labels.shape, jnp.float32))
    assert reductions(bwd.jaxpr, []) == []


def _loss_gauge(trace):
    """`loss/closed_form_calls` after `trace()` ran with telemetry on."""
    tel = get_telemetry()
    was = tel.enabled
    try:
        configure(enabled=True)
        trace()
        return tel.gauges.get("loss/closed_form_calls")
    finally:
        configure(enabled=was)
        tel.reset()


def _tiny_lm_step(accum_steps=1):
    from kfac_pytorch_tpu import KFAC, capture
    from kfac_pytorch_tpu.models import transformer_lm
    from kfac_pytorch_tpu.training import TrainState, make_train_step
    from kfac_pytorch_tpu.training.step import make_sgd

    model = transformer_lm.get_model(50, max_len=16, d_model=32, n_heads=2, n_layers=1)
    toks = np.random.RandomState(0).randint(0, 50, size=(4, 17))
    batch = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    kfac = KFAC(layers=capture.discover_layers(model, batch[0], train=True), damping=0.01, precond_method="inverse")
    params = model.init(jax.random.PRNGKey(0), batch[0], train=True)["params"]
    tx = make_sgd(momentum=0.9)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), kfac_state=kfac.init(params))
    step = make_train_step(model, tx, kfac, train_kwargs={"train": True}, accum_steps=accum_steps)
    if accum_steps > 1:
        batch = jax.tree_util.tree_map(lambda a: a.reshape(accum_steps, -1, *a.shape[1:]), batch)
    return model, state, batch, step


@pytest.mark.parametrize("flags", [
    dict(update_factors=False, update_eigen=False),
    dict(update_factors=True, update_eigen=False),
    dict(update_factors=True, update_eigen=True),
], ids=["plain", "factors", "refresh"])
@pytest.mark.parametrize("accum_steps", [1, 2], ids=["whole_batch", "two_microbatches"])
def test_gauge_counts_one_call_per_step_program(flags, accum_steps):
    """Through `make_train_step`: one closed-form loss in each step program,
    however many programs were traced before it (under accumulation the scan
    body's and the captured tail's each start the count anew)."""
    _, state, batch, step = _tiny_lm_step(accum_steps)
    shapes = jax.eval_shape(lambda: state)

    def trace():
        other = dict(flags, update_factors=not flags["update_factors"], update_eigen=False)
        for f in (other, flags):  # the second program's count starts anew
            step.trace(shapes, batch, jnp.float32(0.1), jnp.float32(0.01), **f)

    assert _loss_gauge(trace) == 1


def test_gauge_is_silent_with_telemetry_off():
    logits, labels = _problem((4, 10))
    step_lib.reset_loss_tally()
    softmax_cross_entropy(logits, labels)
    assert "loss/closed_form_calls" not in get_telemetry().gauges


@pytest.mark.parametrize("flags", [
    dict(update_factors=False, update_eigen=False),
    dict(update_factors=True, update_eigen=True),
], ids=["plain", "refresh"])
def test_step_loss_and_accuracy_are_the_plain_expressions(flags):
    """The step's `loss` and `accuracy` outputs, taken inside `value_and_grad`
    as aux, against the model's logits through the plain expression."""
    model, state, batch, step = _tiny_lm_step()
    logits = model.apply({"params": state.params}, batch[0], train=True)
    want_loss = plain_cross_entropy(logits, batch[1]).mean()
    want_acc = float(jnp.mean(jnp.argmax(logits, -1) == batch[1]))
    _, metrics = step(state, batch, jnp.float32(0.1), jnp.float32(0.01), **flags)
    np.testing.assert_allclose(metrics["loss"], want_loss, rtol=1e-5)
    np.testing.assert_allclose(metrics["accuracy"], want_acc, rtol=1e-6)


def test_masked_eval_sums_rows_and_hits():
    from kfac_pytorch_tpu.training import make_eval_step, make_masked_eval_step

    model, state, batch, _ = _tiny_lm_step()
    logits = model.apply({"params": state.params}, batch[0], train=False)
    mask = jnp.asarray(np.random.RandomState(2).rand(*batch[1].shape) > 0.4, jnp.float32)
    out = make_masked_eval_step(model, eval_kwargs={"train": False})(state, (*batch, mask))
    rows = plain_cross_entropy(logits, batch[1])
    hits = np.asarray(jnp.argmax(logits, -1) == batch[1], np.float32)
    np.testing.assert_allclose(out["loss_sum"], (rows * np.asarray(mask)).sum(), rtol=1e-5)
    np.testing.assert_allclose(out["correct"], (hits * np.asarray(mask)).sum())
    np.testing.assert_allclose(out["count"], np.asarray(mask).sum())
    plain = make_eval_step(model, eval_kwargs={"train": False})(state, batch)
    np.testing.assert_allclose(plain["loss"], rows.mean(), rtol=1e-5)
    np.testing.assert_allclose(plain["accuracy"], hits.mean(), rtol=1e-6)
