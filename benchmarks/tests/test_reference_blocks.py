"""``reference/kfac_sgd.py`` in blocks, on the rehearsal stack of
``reference/bank_stack.py`` at a tiny size: the ``bank`` kind against the same
layers written as dense layers with zeroed rows through the ``dense`` kind;
any grouping of the layers (factors, inverses and momentum then on the host)
and any stack budget of the inverses against one group and one stack."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench

TESTS = os.path.dirname(os.path.abspath(__file__))
kf = bench.load_module(bench.HERE, "reference", "kfac_sgd.py")
weights = bench.load_module(bench.HERE, "weights.py")
bank_stack = bench.load_module(TESTS, "reference", "bank_stack.py")
CFG = bench.load_json(TESTS, "configs", "bank_tiny.json")
HYPER = kf.hyper_of(CFG)
STEPS = 3


def start(seed=5):
    model = bank_stack.Model(CFG)
    params = weights.make_weights(model.param_shapes(), weights.seed_scalar(seed), CFG["weights"])
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], size=(STEPS, CFG["per_chip_batch"], CFG["seq_len"] + 1), dtype=np.int32)
    return model, params, [(b[:, :-1], b[:, 1:]) for b in ids]


def follow(model, params, batches, fac=1, refresh_every=2, **how):
    """Three steps: ``(losses, first gradient as the optimizer gets it, state, residual)``."""
    steps = kf.Steps(model, HYPER, **how)
    state, losses, grad1, worst = steps.init(params), [], None, 0.0
    for k, batch in enumerate(batches):
        state, loss, grads, resid = steps.step(state, batch, jnp.float32(CFG["base_lr"]),
                                               capture=k % fac == 0, refresh=k % refresh_every == 0)
        losses.append(float(loss))
        grad1 = jax.device_get(grads) if k == 0 else grad1
        worst = max(worst, float(resid)) if resid is not None else worst
    return losses, grad1, state, worst


def close(a, b, rtol=1e-4):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_allclose(x, y, rtol=rtol, atol=rtol * max(float(np.abs(y).max()), 1e-30))


def test_weights_of_a_bank_take_their_fan_in_from_the_rows_of_one_expert():
    shapes = {"bank": {"kernel": jax.ShapeDtypeStruct((4, 400, 30), jnp.float32)}}
    w = weights.make_weights(shapes, weights.seed_scalar(1), {"dense_kernel": "lecun_fan_in"})
    assert float(jnp.std(w["bank"]["kernel"])) == pytest.approx(400 ** -0.5, rel=0.05)


def test_every_expert_is_routed_rows_and_not_all():
    model, params, batches = start()
    tape = kf.Tape()
    model.loss(params, batches[0], tape, kf.Precision())
    rows = np.asarray(tape.rows["layer_0/gate"])
    assert rows.shape == (CFG["per_chip_batch"] * CFG["seq_len"], CFG["experts_held"])
    assert set(np.unique(rows)) == {0.0, 1.0} and (0 < rows.sum(axis=0)).all() and rows.mean() < 0.5


@pytest.mark.parametrize("fac", [1, 2])
def test_bank_kind_is_dense_layers_with_zeroed_rows(fac):
    model, params, batches = start()
    dense = bank_stack.Model(CFG, as_dense=True)
    losses, grad1, state, resid = follow(model, params, batches, fac=fac)
    dlosses, dgrad1, dstate, dresid = follow(dense, model.split_banks(params), batches, fac=fac)
    assert losses == pytest.approx(dlosses, rel=1e-6) and resid < 1e-3 and dresid < 1e-3
    split = lambda tree: model.split_banks(tree)
    close(split(grad1), dgrad1)
    close(split(state.params), dstate.params, rtol=1e-6)
    close(split(jax.tree_util.tree_map(jnp.subtract, state.params, params)),
          jax.tree_util.tree_map(jnp.subtract, dstate.params, model.split_banks(params)))
    for layer in model.layers:
        name = layer["name"]
        for kept, dkept in ((state.factors, dstate.factors), (state.inverses, dstate.inverses)):
            if layer["kind"] == "bank":
                for e in range(CFG["experts_held"]):
                    close([f[e] for f in kept[name]], dkept[f"{name}_{e}"])
            else:
                close(kept[name], dkept[name])


@pytest.mark.parametrize("groups,stack_bytes", [(2, kf.STACK_BYTES), (3, 4 * 16 * 16 * 2), (99, 1), (1, 4 * 12 * 12 * 5)])
def test_groups_and_stacks_change_nothing(groups, stack_bytes):
    model, params, batches = start(seed=7)
    losses, grad1, state, resid = follow(model, params, batches)
    glosses, ggrad1, gstate, gresid = follow(model, params, batches, groups=groups, stack_bytes=stack_bytes)
    assert glosses == pytest.approx(losses, rel=1e-6) and gresid == pytest.approx(resid, rel=0.05)
    close(ggrad1, grad1)
    close(gstate.params, state.params, rtol=1e-6)
    close(jax.tree_util.tree_map(jnp.subtract, gstate.params, params),
          jax.tree_util.tree_map(jnp.subtract, state.params, params))
    for kept, gkept in ((state.momentum, gstate.momentum), (state.factors, gstate.factors),
                        (state.inverses, gstate.inverses)):
        close(gkept, kept)
        # between their uses a grouped reference keeps them on the host, a single group on the device
        on_host = {isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(gkept)}
        assert on_host == {groups > 1}
    assert all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(gstate.params))


def test_layer_groups_are_consecutive_and_cover():
    layers = list(range(10))
    for n in (1, 3, 4, 10, 25):
        got = kf.layer_groups(layers, n)
        assert sum(got, []) == layers and len(got) == min(n, 10) and all(got)
        assert max(map(len, got)) - min(map(len, got)) <= 1
