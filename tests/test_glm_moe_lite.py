"""The sparse-expert LM with latent attention (models/glm_moe_lite.py) and its
expert-bank K-FAC against the benchmark's plain reference
(benchmarks/reference/glm_moe_lite.py through reference/kfac_sgd.py::Steps),
at a small size on the CPU: loss, gradients, three K-FAC steps, the
expert-parallel shares adding up to the uncut layer, all-held routing, an
expert with no row, bounded refresh stacks, recomputation."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_pytorch_tpu import KFAC, capture
from kfac_pytorch_tpu.models import glm_moe_lite
from kfac_pytorch_tpu.models.layers import KFAC_ACTS, STEP_SCALARS
from kfac_pytorch_tpu.ops import factors as factor_ops
from kfac_pytorch_tpu.ops import precondition as precond_ops
from kfac_pytorch_tpu.training.step import TrainState, kfac_flags_for_step, make_sgd, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    name = "glm_test_" + "_".join(parts)[:-3].replace(os.sep, "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


kf = _load("reference", "kfac_sgd.py")
reference = _load("reference", "glm_moe_lite.py")
weights = _load("weights.py")

# hidden 64, 16 experts of which 4 are held, top-2, a dense and two expert layers, 256 ids
CFG = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48, "num_attention_heads": 4,
    "n_routed_experts": 4, "published": {"n_routed_experts": 16}, "held_experts": [0, 4],
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "rms_norm_eps": 1e-5, "rope_theta": 1e6, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 20, "vocab_size": 256,
    "base_lr": 0.01, "momentum": 0.9, "weight_decay": 1e-5, "grad_clip": 0.25,
    "kfac": {"damping": 0.003, "stat_decay": 0.95, "kl_clip": 0.001, "precond_method": "inverse",
             "max_factor_side": 64},
    "weights": {"dense_kernel": 0.02, "embedding": 0.02, "scale": 1.0},
}
SEQ, BATCH = 32, 2


def cfg_with(**changes):
    cfg = {**CFG, **{k: v for k, v in changes.items() if k != "published"}}
    if "held_experts" in changes:
        cfg["n_routed_experts"] = changes["held_experts"][1]
    if "published" in changes:
        cfg["published"] = {"n_routed_experts": changes["published"]}
    return cfg


def sizes_of(cfg):
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "rope_theta")
    return dict({k: cfg[k] for k in keys}, first_k_dense=cfg["first_k_dense_replace"],
                n_routed_experts=cfg["published"]["n_routed_experts"], held=tuple(cfg["held_experts"]),
                kfac_max_side=cfg["kfac"]["max_factor_side"], kfac_exclude=tuple(cfg["kfac"].get("exclude", ())))


def program(cfg, remat=True, fac_freq=1, kfac_freq=10, **kfac_kwargs):
    """(model, kfac, step, state from the benchmark's weights of seed 7, p0)."""
    model = glm_moe_lite.get_model(remat=remat, **sizes_of(cfg))
    toks = jnp.zeros((BATCH, SEQ), jnp.int32)
    layers = capture.discover_layers(model, toks, train=True)
    k = cfg["kfac"]
    kfac = KFAC(layers=layers, shared_a=glm_moe_lite.shared_inputs(layers), factor_decay=k["stat_decay"],
                damping=k["damping"], kl_clip=k["kl_clip"], fac_update_freq=fac_freq,
                kfac_update_freq=kfac_freq, precond_method="inverse", **kfac_kwargs)
    tx = make_sgd(cfg["momentum"], cfg["weight_decay"])
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), toks, train=True))["params"]
    p0 = weights.make_weights(shapes, weights.seed_scalar(7), cfg["weights"])
    copy = jax.tree_util.tree_map(jnp.array, p0)  # the step donates its state
    state = TrainState(step=jnp.zeros((), jnp.int32), params=copy, batch_stats={},
                       opt_state=tx.init(copy), kfac_state=kfac.init(copy))
    step = make_train_step(model, tx, kfac, train_kwargs={"train": True}, grad_clip=cfg["grad_clip"])
    return model, kfac, step, state, p0


def batches(n, seed=3, vocab=256, seq=SEQ, batch=BATCH):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
        out.append((jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])))
    return out


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def worst_leaf(tree_a, tree_b):
    gaps = jax.tree_util.tree_map(rel, tree_a, tree_b)
    return max(jax.tree_util.tree_leaves(gaps))


def test_discovery_names_banks_and_shared_inputs():
    model, kfac, *_ = program(CFG)
    assert "layer_1/mlp/gate#b4" in kfac.layers and "layer_0/attn/q_a" in kfac.layers
    assert not any(n.startswith("layer_0/mlp") for n in kfac.layers)  # width 160 over the 64 bound: SGD
    assert kfac.shared_a["layer_2/mlp/up#b4"] == "layer_2/mlp/gate#b4"
    assert kfac.shared_a["layer_1/mlp/router"] == "layer_1/mlp/shared_gate"
    assert kfac.shared_a["layer_0/attn/kv_a"] == "layer_0/attn/q_a"
    assert len(kfac.layers) == len(reference.Model(CFG).layers) == 2 + 2 * 9


def test_loss_and_gradients_match_the_reference():
    model, _, _, state, p0 = program(CFG)
    batch = batches(1)[0]

    def loss_fn(params):
        logits = model.apply({"params": params}, batch[0], train=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, batch[1][..., None], axis=-1))

    loss, grads = jax.value_and_grad(loss_fn)(p0)
    ref = reference.Model(CFG)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: ref.loss(p, batch, kf.Tape(only=()), kf.Precision()))(p0)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    assert worst_leaf(grads, ref_grads) < 2e-4


def follow(cfg, n_steps=3, data=None, **program_kwargs):
    """The program and the reference over ``n_steps`` steps of the LM
    trainer's default schedule from the same weights: ``(program's params,
    reference's, step metrics, first momentum of both, the starting weights)``."""
    _, kfac, step, state, p0 = program(cfg, **program_kwargs)
    data = data or batches(n_steps)
    steps = kf.Steps(reference.Model(cfg), kf.hyper_of(cfg))
    ref, metrics, first = steps.init(p0), [], None
    for k in range(n_steps):
        flags = kfac_flags_for_step(k, kfac)
        state, m = step(state, data[k], jnp.float32(cfg["base_lr"]), jnp.float32(cfg["kfac"]["damping"]), **flags)
        ref, loss, _, _ = steps.step(ref, data[k], jnp.float32(cfg["base_lr"]),
                                     capture=flags["update_factors"], refresh=flags["update_eigen"])
        metrics.append({name: float(v) for name, v in m.items()})
        assert abs(metrics[-1]["loss"] - float(loss)) < 2e-5 * float(loss)
        if k == 0:
            trace = next(s.trace for s in state.opt_state if hasattr(s, "trace"))
            first = (jax.tree_util.tree_map(jnp.array, trace), ref.momentum)
    return state, ref, metrics, first, p0


def test_three_kfac_steps_match_the_reference():
    # steps 0, 1, 2 are refresh (with capture), factors, factors: capture,
    # refresh, apply, the KL clip and SGD of both programs
    state, ref, metrics, (trace, ref_momentum), p0 = follow(CFG)
    assert worst_leaf(trace, ref_momentum) < 2e-3  # the first gradient as the optimizer got it
    delta = jax.tree_util.tree_map(lambda a, b: a - b, state.params, ref.params)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, ref.params, p0)
    gaps = jax.tree_util.tree_map(lambda d, m: float(jnp.linalg.norm(d) / jnp.linalg.norm(m)), delta, moved)
    assert max(jax.tree_util.tree_leaves(gaps)) < 5e-3
    # the running averages themselves, banks and shared inputs
    facs = state.kfac_state["factors"]
    for name in ("layer_1/mlp/down#b4", "layer_2/mlp/gate#b4", "layer_1/mlp/shared_gate", "layer_0/attn/q_a"):
        a, g = ref.factors[capture.layer_base(name)]
        assert rel(facs[name]["A"], a) < 1e-4 and rel(facs[name]["G"], g) < 1e-4
    assert "A" not in facs["layer_2/mlp/up#b4"] and "A" not in facs["layer_1/mlp/router"]
    assert rel(facs["layer_2/mlp/up#b4"]["G"], ref.factors["layer_2/mlp/up"][1]) < 1e-4
    assert all(m["moe_dropped_rows"] == 0.0 and m["moe_held_rows"] > 0 for m in metrics)
    assert all(1.0 <= m["moe_load_max_over_mean"] <= 4.0 for m in metrics)


@pytest.mark.parametrize("exclude", [("dense_layers", "shared_expert"), ("dense_layers", "shared_expert", "down_banks")])
def test_groups_left_to_sgd_follow_the_reference(exclude):
    cfg = {**CFG, "kfac": {**CFG["kfac"], "exclude": list(exclude)}}
    _, kfac, *_ = program(cfg)
    assert not any(n.startswith("layer_0/") or "shared" in n for n in kfac.layers)
    assert ("layer_1/mlp/down#b4" in kfac.layers) == ("down_banks" not in exclude)
    assert "layer_1/mlp/router" not in kfac.shared_a  # the router owns its input's A now
    assert len(kfac.layers) == len(reference.Model(cfg).layers) == 2 * (6 - ("down_banks" in exclude))
    state, ref, _, (trace, ref_momentum), p0 = follow(cfg)
    assert worst_leaf(trace, ref_momentum) < 2e-3
    assert worst_leaf(state.params, ref.params) < 1e-4
    with pytest.raises(ValueError, match="names no group"):
        program({**CFG, "kfac": {**CFG["kfac"], "exclude": ["everything"]}})


def expert_layer(cfg, params, h):
    c = glm_moe_lite.GLMMoELiteConfig(**sizes_of(cfg))
    layer = glm_moe_lite.ExpertMLP(
        c.n_routed_experts, c.num_experts_per_tok, c.routed_scaling_factor, c.moe_intermediate_size,
        tuple(c.held), c.kfac_max_side)
    return layer.apply({"params": params}, h)


def bank_weights(n_experts=16, seed=5):
    shapes = {"router": (64, 16), "shared_gate": (64, 48), "shared_up": (64, 48), "shared_down": (48, 64),
              "gate": (n_experts, 64, 48), "up": (n_experts, 64, 48), "down": (n_experts, 48, 64)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {n: {"kernel": 0.1 * jax.random.normal(k, s)} for k, (n, s) in zip(keys, shapes.items())}


def test_the_shares_add_up_to_the_uncut_layer():
    full = bank_weights()
    h = jax.random.normal(jax.random.PRNGKey(1), (BATCH, SEQ, 64))
    uncut = reference.Model(cfg_with(held_experts=[0, 16]))
    with jax.default_matmul_precision("highest"):
        whole = uncut._experts(kf.Tape(only=()), "layer_1/mlp", full, h, kf.Precision())
    shared_only = {**full, **{n: {"kernel": jnp.zeros_like(full[n]["kernel"][:4])} for n in ("gate", "up", "down")}}
    shared = expert_layer(cfg_with(held_experts=[0, 4]), shared_only, h)[0]  # zero banks: the shared expert alone
    total, held_rows = shared, 0.0
    for first in (0, 4, 8, 12):
        part = {**full, **{n: {"kernel": full[n]["kernel"][first:first + 4]} for n in ("gate", "up", "down")}}
        y, scalars = expert_layer(cfg_with(held_experts=[first, 4]), part, h)
        total = total + (y - shared)  # what every rank computes alike counted once
        held_rows += float(scalars["moe_held_rows"])
    assert held_rows == BATCH * SEQ * CFG["num_experts_per_tok"]  # every pair lands on exactly one rank
    assert rel(total, whole) < 1e-5


def test_all_held_routing_equals_the_reference():
    # every token's experts are held here: all T * k pairs are multiplied
    cfg = cfg_with(held_experts=[0, 4], published=4)
    params = bank_weights(4)
    params["router"] = {"kernel": params["router"]["kernel"][:, :4]}
    h = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, 64))
    y, scalars = expert_layer(cfg, params, h)
    assert float(scalars["moe_held_rows"]) == BATCH * SEQ * 2 and float(scalars["moe_dropped_rows"]) == 0
    with jax.default_matmul_precision("highest"):
        want = reference.Model(cfg)._experts(kf.Tape(only=()), "layer_1/mlp", params, h, kf.Precision())
    assert rel(y, want) < 1e-5
    state, ref, metrics, _, _ = follow(cfg)
    assert worst_leaf(state.params, ref.params) < 1e-4
    assert all(m["moe_held_rows"] == 2 * BATCH * SEQ * 2 for m in metrics)  # two expert layers


def test_an_expert_with_no_row_decays_and_stays_finite():
    data = batches(3, seed=11, seq=4, batch=1)  # 4 tokens, 8 pairs over 16 experts: held experts go empty
    state, ref, metrics, _, _ = follow(CFG, data=data)
    assert min(m["moe_held_rows"] for m in metrics) < 2 * 4  # fewer rows than held experts in the two layers
    assert all(np.isfinite(m["loss"]) for m in metrics)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree_util.tree_leaves(state))
    assert worst_leaf(state.params, ref.params) < 1e-4
    # an expert no row reached moved its averages by the decay alone: 0.95^3 of the identity
    down = state.kfac_state["factors"]["layer_1/mlp/down#b4"]["A"]
    traces = jnp.trace(down, axis1=-2, axis2=-1) / down.shape[-1]
    assert float(jnp.min(traces)) == pytest.approx(0.95 ** 3, rel=1e-5)
    assert rel(down, ref.factors["layer_1/mlp/down"][0]) < 1e-4


@pytest.mark.parametrize("matrices", [1, 3, 7])
def test_bounded_refresh_stacks_equal_one_stack(matrices, monkeypatch):
    # held of 8: the tables of side 64 hold 2 + 2 x (5 + 16) + ... inverses
    cfg = cfg_with(held_experts=[0, 8])
    one, *_ = follow(cfg, n_steps=1)
    layout_one = program(cfg)[1]._inverse_layout(one.kfac_state["factors"])[0]
    monkeypatch.setattr(precond_ops, "TABLE_BATCH_BYTES", matrices * 4 * 64 * 64)
    some, *_ = follow(cfg, n_steps=1)
    layout_some = program(cfg)[1]._inverse_layout(some.kfac_state["factors"])[0]
    assert layout_one.keys() == layout_some.keys()
    for name in layout_one:
        for key in ("iA", "iG"):
            (side, a0, rows), (_, b0, _) = layout_one[name][key], layout_some[name][key]
            a = one.kfac_state["inverse_tables"][str(side)][a0:a0 + rows]
            b = some.kfac_state["inverse_tables"][str(side)][b0:b0 + rows]
            assert rel(b, a) < 1e-6, (name, key)
    assert worst_leaf(some.params, one.params) < 1e-6


def test_recomputation_changes_nothing_and_sows_once():
    with_remat, *_ = follow(CFG, n_steps=2, remat=True)
    without, *_ = follow(CFG, n_steps=2, remat=False)
    assert worst_leaf(with_remat.params, without.params) < 1e-6
    assert worst_leaf(with_remat.kfac_state["factors"], without.kfac_state["factors"]) < 1e-6
    # the recomputed forward pass multiplies no statistic again: as many
    # per-expert Gram products in the program with recomputation as without
    model, kfac, step, state, _ = program(CFG, remat=True)
    plain = program(CFG, remat=False)
    count = lambda st, s: st.lower(s, batches(1)[0], jnp.float32(0.01), jnp.float32(0.003),
                                   update_factors=True, update_eigen=False).as_text().count("ragged_dot_general")
    assert count(step, state) == count(plain[2], plain[3])


def test_bank_statistics_follow_the_rows_of_each_expert():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((12, 6)), jnp.float32)
    sizes = jnp.asarray([3, 0, 5], jnp.int32)  # 4 rows past the groups count for none
    a = factor_ops.compute_a_bank(x, sizes, n_tokens=10)
    g = factor_ops.compute_g_bank(x, sizes, jnp.int32(10), batch_averaged=True)
    for e, (lo, hi) in enumerate(((0, 3), (3, 3), (3, 8))):
        want = x[lo:hi].T @ x[lo:hi]
        np.testing.assert_allclose(a[e], want / 10, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(g[e], want * 10, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernels", [False, True], ids=["ragged_dot", "pallas"])
def test_grouped_products_zero_the_rows_past_the_groups(kernels, monkeypatch):
    """Both paths of ops/grouped.py (XLA's ragged_dot, and with one device the
    Pallas grouped kernels in the interpreter) against a loop over the groups;
    rows past the groups are zero forward and backward, whatever the kernel
    leaves there (the interpreter leaves NaN)."""
    from kfac_pytorch_tpu.ops import grouped

    if kernels:
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert grouped._use_kernels(256) is kernels
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((256, 24)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 24, 40)), jnp.float32)
    sizes = jnp.asarray([70, 0, 100], jnp.int32)
    lo = [0, 70, 70]

    def by_hand(x, w):
        parts = [x[lo[e]:lo[e] + int(sizes[e])] @ w[e] for e in range(3)]
        return jnp.concatenate(parts + [jnp.zeros((256 - 170, 40))])

    y = grouped.grouped_matmul(x, w, sizes)
    np.testing.assert_allclose(y, by_hand(x, w), rtol=1e-5, atol=1e-5)
    loss = lambda f: (lambda x, w: jnp.sum(jnp.sin(f(x, w))))
    got = jax.grad(loss(lambda x, w: grouped.grouped_matmul(x, w, sizes)), (0, 1))(x, w)
    want = jax.grad(loss(by_hand), (0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
    assert bool(jnp.all(got[0][170:] == 0)) and bool(jnp.all(y[170:] == 0))
    gram = grouped.grouped_gram(x, sizes)
    for e in range(3):
        rows = x[lo[e]:lo[e] + int(sizes[e])]
        np.testing.assert_allclose(gram[e], rows.T @ rows, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("count,per", [(5, 2), (4, 4), (7, 3), (1, 1)])
def test_tables_a_batch_at_a_time_equal_the_inverses_layer_by_layer(count, per, monkeypatch):
    rng = np.random.default_rng(count)
    m = rng.standard_normal((count, 16, 16)).astype(np.float32)
    spd = jnp.asarray(m @ m.transpose(0, 2, 1) + 0.5 * np.eye(16, dtype=np.float32))
    facs = {f"l{i}": {"A": spd[i], "G": spd[(i + 1) % count][:8, :8]} for i in range(count)}
    whole = precond_ops.factored_inverse_all(facs, jnp.float32(0.003))
    monkeypatch.setattr(precond_ops, "TABLE_BATCH_BYTES", per * 4 * 16 * 16)
    shapes = {n: {k: tuple(v.shape) for k, v in f.items()} for n, f in facs.items()}
    layout, rows = precond_ops.inverse_table_layout(shapes, {})
    assert rows[16] % min(per, count) == 0 and count <= rows[16] < count + per
    tables = {str(side): jnp.broadcast_to(jnp.eye(side), (k, side, side)) for side, k in rows.items()}
    tables = precond_ops.factored_inverse_tables(facs, tables, jnp.float32(0.003), 1e-10, {}, layout)
    for name, where in layout.items():
        for key, (side, first, _) in where.items():
            assert rel(tables[str(side)][first], whole[name][key]) < 1e-6, (name, key)
    for side, k in rows.items():  # rows past the inverses stay at the identity
        used = sum(n for where in layout.values() for s, _, n in where.values() if s == side)
        assert bool(jnp.all(tables[str(side)][used:] == jnp.eye(side)))


def test_the_trainers_build_wires_the_model(monkeypatch):
    # the trainer's import places the persistent compile cache (examples/_env.py) for the rest of this
    # process: a later test's program then aborts on a hit (the ring-attention step does), so not here
    from kfac_pytorch_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "enable_persistent_cache", lambda: "")
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import train_transformer_lm as trainer

    built = trainer.build(
        "glm_moe_lite", sizes_of(CFG), global_batch=BATCH, seq_len=SEQ,
        attention_fn=glm_moe_lite.full_attention, remat=True,
        kfac_kwargs=dict(factor_decay=0.95, damping=0.003, kl_clip=0.001, fac_update_freq=1,
                         kfac_update_freq=10, precond_method="inverse"))
    assert built["kfac"].shared_a and built["kfac"].inverse_tables
    state = built["init_state"](0)
    state, metrics = built["train_step"](state, batches(1)[0], jnp.float32(0.01), jnp.float32(0.003),
                                         **kfac_flags_for_step(0, built["kfac"]))
    assert np.isfinite(float(metrics["loss"])) and float(metrics["moe_dropped_rows"]) == 0.0
    with pytest.raises(ValueError, match="unknown model"):
        trainer.build("nope", {}, global_batch=1, seq_len=8, attention_fn=None)


def test_banks_and_shared_inputs_refuse_other_paths():
    layers = ["a/gate#b4", "a/up#b4"]
    with pytest.raises(ValueError, match="replicated inverse path"):
        KFAC(layers=layers, shared_a={"a/up#b4": "a/gate#b4"})  # the eigen method
    with pytest.raises(ValueError, match="shared_a maps"):
        KFAC(layers=layers, shared_a={"a/up#b4": "elsewhere"}, precond_method="inverse")


def test_step_scalars_reach_the_metrics_only_where_a_model_sows_them():
    model = glm_moe_lite.get_model(**sizes_of(CFG))
    toks = jnp.zeros((BATCH, SEQ), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), toks, train=True)
    _, mut = model.apply({"params": variables["params"]}, toks, train=True, mutable=[STEP_SCALARS, KFAC_ACTS])
    assert set(mut[STEP_SCALARS]) == {"moe_held_rows", "moe_load_max_over_mean", "moe_dropped_rows"}
    assert "a_bank" in mut[KFAC_ACTS]["layer_1"]["mlp"]["gate"]
    assert "a_shared" in mut[KFAC_ACTS]["layer_1"]["mlp"]["up"]
