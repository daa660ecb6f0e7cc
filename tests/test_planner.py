"""planner/ contracts: cost-model monotonicity, the composition validity
matrix vs the REAL refusal behavior, profile inertness, autotune
determinism, plan checkpointing, and the exact compile budget.

The matrix test is the load-bearing one: profiles.RULES claims to encode
every refusal path the six levers introduced, and the only way that claim
stays true is to hold the matrix and the enforcement points
(KFAC.__init__ / KFAC.init / training.step.require_pure_dp_mesh) to the
same answer for every (lever, environment) pair — both directions: every
predicted violation actually refuses, and every predicted-valid pair
actually constructs.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kfac_pytorch_tpu import KFAC, capture
from kfac_pytorch_tpu.compile_cache import expected_step_variants
from kfac_pytorch_tpu.models.layers import KFACDense, KFACEmbed
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh
from kfac_pytorch_tpu.planner import (
    ModelFacts,
    Plan,
    PlanEnv,
    autotune,
    candidate_plans,
    model_facts,
    resolve_profile,
    violations,
)
from kfac_pytorch_tpu.planner.profiles import REFUSAL_RULES, fit_plan
from kfac_pytorch_tpu.training.step import (
    TrainState,
    make_sgd,
    make_train_step,
    require_pure_dp_mesh,
)

# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

# all factor sides < 512: the truncated solver must never engage
_SMALL_FACTS = ModelFacts(
    shapes={f"conv{i}": (64, 288) for i in range(12)}, has_conv=True
)
# CIFAR-ResNet-like: 576-wide A sides — a big refresh relative to the
# every-step rotation work, but not enough rsvd speedup to truncate
_MEDIUM_FACTS = ModelFacts(
    shapes={f"conv{i}": (64, 576) for i in range(30)}, has_conv=True
)
# ResNet-50-like: 2304/4608-wide sides where truncation wins big
_BIG_FACTS = ModelFacts(
    shapes={
        **{f"mid{i}": (256, 2304) for i in range(6)},
        **{f"deep{i}": (512, 4608) for i in range(3)},
        "fc": (1000, 2049),
    },
    has_conv=True,
)


def _env(world=8, axes=("data",), **kw):
    return PlanEnv(world=world, mesh_axes=axes if world > 1 else (), **kw)


# ---------------------------------------------------------------------------
# cost-model monotonicity
# ---------------------------------------------------------------------------


def test_bigger_sides_engage_streaming():
    """Where truncation wins, production now engages the streaming solver
    (rsvd layout + per-step folds) rather than periodic rsvd — the refresh
    spike disappears instead of shrinking."""
    env = _env(world=8)
    small, _, _ = resolve_profile("production", _SMALL_FACTS, env)
    big, report, _ = resolve_profile("production", _BIG_FACTS, env)
    assert small.solver == "eigh"
    assert big.solver == "streaming"
    assert big.stream_drift_threshold > 0.0
    assert report.rsvd_speedup >= 2.0


def test_more_devices_engage_owner_monotonically():
    """Once the world is big enough for owner sharding, every bigger
    world keeps it — the lever must be monotone in device count."""
    engaged = [
        resolve_profile(
            "production", _BIG_FACTS, _env(world=w)
        )[0].factor_sharding
        == "owner"
        for w in (1, 2, 4, 8, 16, 32, 64)
    ]
    assert engaged == sorted(engaged)  # False... then True...
    assert engaged[-1] and not engaged[0]


def test_refresh_heavy_models_chunk_the_refresh():
    env = _env(world=8)
    small, _, _ = resolve_profile("production", _SMALL_FACTS, env)
    medium, _, _ = resolve_profile("production", _MEDIUM_FACTS, env)
    assert small.eigh_chunks == 1
    assert medium.eigh_chunks > 1
    # the scheduler clamps k_eff to the refresh interval; the plan must too
    tight, _, _ = resolve_profile(
        "production", _MEDIUM_FACTS, _env(world=8, kfac_update_freq=1)
    )
    assert tight.eigh_chunks == 1


def test_memory_profile_never_chunks():
    """eigh_chunks>1 double-buffers the eigen state (eigen_pending) — the
    opposite of a memory win — so the memory profile must keep it off."""
    for facts in (_SMALL_FACTS, _BIG_FACTS):
        plan, _, _ = resolve_profile("memory", facts, _env(world=8))
        assert plan.eigh_chunks == 1
        assert plan.factor_sharding == "owner"


def test_production_resolves_composed_plan_at_scale():
    """The acceptance bar: ≥3 non-default levers on big shapes at world
    32 (the exact ResNet-50 plan is pinned by check_plan_snapshot.py)."""
    plan, _, dropped = resolve_profile(
        "production", _BIG_FACTS, _env(world=32)
    )
    assert len(plan.non_default_levers()) >= 3
    assert not dropped


def test_model_facts_matches_init_factor_shapes():
    """model_facts must derive the SAME (g, a) sides init() builds
    factors with — the cost model prices what the runtime allocates."""

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(8, (3, 3), name="plain_conv")(x)  # not captured
            from kfac_pytorch_tpu.models.layers import KFACConv

            x = KFACConv(8, (3, 3), name="conv")(x)
            x = x.reshape((x.shape[0], -1))
            return KFACDense(10, name="fc")(x)

    params = Net().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)), train=True
    )["params"]
    facts = model_facts(params)
    kfac = KFAC(damping=0.01)
    state = kfac.init(params)
    init_shapes = {
        name: (int(f["G"].shape[0]), int(f["A"].shape[0]))
        for name, f in state["factors"].items()
    }
    assert facts.shapes == init_shapes
    assert facts.has_conv and not facts.has_diag_a


# ---------------------------------------------------------------------------
# pairwise composition-validity matrix vs the real refusals
# ---------------------------------------------------------------------------

_LEVERS = {
    "chunks": Plan(eigh_chunks=2),
    "kernel": Plan(factor_kernel="pallas"),
    "comm_dtype": Plan(factor_comm_dtype="bf16"),
    "comm_freq": Plan(factor_comm_freq=2),
    "rsvd": Plan(solver="rsvd"),
    "owner": Plan(factor_sharding="owner"),
    "owner+chunks": Plan(factor_sharding="owner", eigh_chunks=2),
    "rsvd+comm": Plan(solver="rsvd", factor_comm_dtype="bf16"),
    "overlap": Plan(comm_overlap=True),
    "overlap+staleness": Plan(
        comm_overlap=True, staleness_budget=1, eigh_chunks=2
    ),
    # budget with nothing to slip: refused by the constructor in EVERY env
    "staleness_bare": Plan(staleness_budget=1),
    "streaming": Plan(solver="streaming"),
    # the two streaming exclusions (constructor-enforced in every env)
    "streaming+chunks": Plan(solver="streaming", eigh_chunks=2),
    "streaming+staleness": Plan(
        solver="streaming", staleness_budget=1, factor_comm_freq=2
    ),
    # curvature service: valid alone (and env rules trip it under
    # inverse / diag_blocks); each plan-internal exclusion gets a pair
    "service": Plan(service_devices=1),
    "service+staleness": Plan(service_devices=1, staleness_budget=1),
    "service+streaming": Plan(service_devices=1, solver="streaming"),
    "service+chunks": Plan(service_devices=1, eigh_chunks=2),
    "service+owner": Plan(service_devices=1, factor_sharding="owner"),
    # int8 wire: valid only WITH deferral and WITHOUT owner sharding —
    # the bare dtype is refused in every env, the composed pair only
    # against the envs that refuse deferral (moe, multi_axis)
    "wire8": Plan(factor_comm_dtype="int8", factor_comm_freq=2),
    "wire8_bare": Plan(factor_comm_dtype="int8"),
    "wire8+owner": Plan(
        factor_comm_dtype="int8", factor_comm_freq=2,
        factor_sharding="owner",
    ),
}

# environment features, each mapping to (PlanEnv kwargs, KFAC kwargs)
_ENVS = {
    "default_dp8": (dict(), dict()),
    "inverse": (dict(precond_method="inverse"), dict(precond_method="inverse")),
    "diag_blocks": (dict(diag_blocks=2), dict(diag_blocks=2)),
    "dist_precond": (
        dict(distribute_precondition=True),
        dict(distribute_precondition=True),
    ),
    "diagnostics": (
        dict(track_diagnostics=True),
        dict(track_diagnostics=True),
    ),
    "multi_axis": (dict(axes=("data", "seq")), dict()),
    "single_device": (dict(world=1), dict()),
    # shardwise model facts (kfac_pytorch_tpu/shardwise/): the KFAC kwargs
    # carry shard-suffixed layer names so the constructor derives the same
    # has_shard_lens/has_moe facts the env kwargs declare
    "shard_lens": (
        dict(has_shard_lens_layers=True),
        dict(layers=["block_0/ff1#c2", "block_0/ff2#r2"]),
    ),
    "moe": (
        dict(has_moe_layers=True),
        dict(layers=["block_0/moe#e4"]),
    ),
    # env-vs-env rows (shard_lens_vs_inverse / _vs_diag_blocks) need the
    # conflicting env features combined in ONE entry
    "shard_lens_inverse": (
        dict(has_shard_lens_layers=True, precond_method="inverse"),
        dict(layers=["block_0/ff1#c2"], precond_method="inverse"),
    ),
    "shard_lens_diag_blocks": (
        dict(has_shard_lens_layers=True, diag_blocks=2),
        dict(layers=["block_0/ff1#c2"], diag_blocks=2),
    ),
    # expert banks ("#b" names) hold their inverses in tables, a layout of
    # the replicated inverse path alone (inverse_tables_vs_other_paths)
    "banks": (
        dict(has_inverse_tables=True, precond_method="inverse"),
        dict(layers=["block_0/experts#b4"], precond_method="inverse"),
    ),
}


def _mesh_for(env_name):
    if env_name == "single_device":
        return None
    devices = np.asarray(jax.devices())
    if env_name == "multi_axis":
        return Mesh(devices.reshape(4, 2), ("data", "seq"))
    return data_parallel_mesh()


@pytest.mark.parametrize("lever_name", sorted(_LEVERS))
@pytest.mark.parametrize("env_name", sorted(_ENVS))
def test_validity_matrix_matches_constructor(lever_name, env_name):
    """Both directions, every pair: constructor-enforced rules the matrix
    predicts must raise ValueError, and pairs the matrix calls valid (or
    merely degrade / init- / train-step-enforced) must construct."""
    plan = _LEVERS[lever_name]
    env_kw, kfac_kw = _ENVS[env_name]
    env_kw = dict(env_kw)
    axes = env_kw.pop("axes", ("data",))
    world = env_kw.pop("world", 8)
    env = PlanEnv(
        world=world, mesh_axes=axes if world > 1 else (), **env_kw
    )
    bad = violations(plan, env)
    mesh = _mesh_for(env_name)
    construct = lambda: KFAC(  # noqa: E731
        damping=0.01, mesh=mesh, **kfac_kw, **plan.kfac_kwargs()
    )
    constructor_rules = [r for r in bad if r.enforced_by == "constructor"]
    if constructor_rules:
        with pytest.raises(ValueError):
            construct()
        return
    kfac = construct()
    # train-step-enforced: the comm levers on a multi-axis mesh construct
    # fine but the explicit-collective wrapper refuses the mesh (a real
    # second axis — 'tensor*' axes are exempt, parallel/mesh.py)
    if any(r.enforced_by == "train_step" for r in bad):
        with pytest.raises(ValueError, match="data-plane mesh"):
            require_pure_dp_mesh(kfac.mesh)


def test_matrix_grid_exercises_every_refusal_rule():
    """Completeness: the pairwise grid above must trip every refusal rule
    at least once — otherwise the matrix has rows no test holds to
    reality."""
    tripped = set()
    for plan in _LEVERS.values():
        for env_kw, _ in _ENVS.values():
            env_kw = dict(env_kw)
            axes = env_kw.pop("axes", ("data",))
            world = env_kw.pop("world", 8)
            env = PlanEnv(
                world=world, mesh_axes=axes if world > 1 else (), **env_kw
            )
            tripped |= {r.name for r in violations(plan, env)}
    expected = {r.name for r in REFUSAL_RULES}
    assert expected <= tripped, expected - tripped


# One smallest KFAC(...) call per constructor-enforced row, tripping that
# row alone: the constructor holds no refusal text of its own, so WHICH rule
# an error names is the row's. "mesh" is "dp" (8 x data), "seq" (4 x data,
# 2 x seq) or absent (no mesh).
_LENS, _MOE, _BANK = ["blk/ff1#c2"], ["blk/moe#e4"], ["blk/experts#b4"]
_ROW_CASES = {
    "chunks_vs_inverse": dict(eigh_chunks=2, precond_method="inverse"),
    "rsvd_vs_inverse": dict(solver="rsvd", precond_method="inverse"),
    "rsvd_vs_diag_blocks": dict(solver="rsvd", diag_blocks=2),
    "owner_vs_inverse": dict(
        factor_sharding="owner", precond_method="inverse", mesh="dp"),
    "owner_vs_diag_blocks": dict(
        factor_sharding="owner", diag_blocks=2, mesh="dp"),
    "owner_vs_distribute_precondition": dict(
        factor_sharding="owner", distribute_precondition=True, mesh="dp"),
    "owner_vs_diagnostics": dict(
        factor_sharding="owner", track_diagnostics=True, mesh="dp"),
    "owner_vs_multi_axis_mesh": dict(factor_sharding="owner", mesh="seq"),
    "streaming_vs_chunks": dict(solver="streaming", eigh_chunks=2),
    "streaming_vs_swap_slip": dict(
        solver="streaming", staleness_budget=1, factor_comm_freq=2),
    "service_vs_inverse": dict(service_devices=1, precond_method="inverse"),
    "service_vs_streaming": dict(service_devices=1, solver="streaming"),
    "service_vs_chunks": dict(service_devices=1, eigh_chunks=2),
    "service_vs_diag_blocks": dict(service_devices=1, diag_blocks=2),
    "service_vs_owner_sharding": dict(
        service_devices=1, factor_sharding="owner", mesh="dp"),
    "shard_lens_vs_inverse": dict(layers=_LENS, precond_method="inverse"),
    "shard_lens_vs_diag_blocks": dict(layers=_LENS, diag_blocks=2),
    "shard_lens_vs_owner_sharding": dict(
        layers=_LENS, factor_sharding="owner", mesh="dp"),
    "moe_vs_owner_sharding": dict(
        layers=_MOE, factor_sharding="owner", mesh="dp"),
    "shard_lens_vs_chunks": dict(layers=_LENS, eigh_chunks=2),
    "shard_lens_vs_streaming": dict(layers=_MOE, solver="streaming"),
    "moe_vs_deferred_comm": dict(layers=_MOE, factor_comm_freq=2, mesh="dp"),
    "service_vs_shard_lens": dict(layers=_LENS, service_devices=1),
    "inverse_tables_vs_other_paths": dict(
        layers=_BANK, precond_method="inverse", distribute_precondition=True,
        mesh="dp"),
    "int8_wire_requires_deferral": dict(factor_comm_dtype="int8", mesh="dp"),
    "int8_wire_vs_owner_sharding": dict(
        factor_comm_dtype="int8", factor_comm_freq=2,
        factor_sharding="owner", mesh="dp"),
    "staleness_requires_slack": dict(staleness_budget=1),
}


@pytest.mark.parametrize(
    "rule_name",
    [r.name for r in REFUSAL_RULES if r.enforced_by == "constructor"],
)
def test_constructor_names_the_row_it_refuses_by(rule_name):
    kw = dict(_ROW_CASES[rule_name])
    mesh = kw.pop("mesh", None)
    if mesh is not None:
        mesh = _mesh_for("multi_axis" if mesh == "seq" else "default_dp8")
    with pytest.raises(ValueError) as err:
        KFAC(damping=0.01, mesh=mesh, **kw)
    named = [r.name for r in REFUSAL_RULES if f"[{r.name}]" in str(err.value)]
    assert named == [rule_name], str(err.value)


def test_owner_accepts_diag_a_layers():
    """PR-6's owner_vs_diag_a_layers refusal is gone: owner sharding lays
    embedding A factors out as [vocab] vector slots (v-groups), so the
    matrix predicts valid AND init actually builds the sharded state."""

    class EmbedNet(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = KFACEmbed(16, 8, name="emb")(x)
            return KFACDense(4, name="fc")(x.mean(axis=1))

    toks = jnp.zeros((2, 3), jnp.int32)
    model = EmbedNet()
    params = model.init(jax.random.PRNGKey(0), toks, train=True)["params"]
    # embeddings are only captured when explicitly discovered (the LM
    # trainer's path) — the default layer set excludes them
    from kfac_pytorch_tpu import capture

    layers = capture.discover_layers(model, toks, train=False)
    facts = model_facts(params, layers=layers)
    assert facts.has_diag_a
    env = _env(world=8, has_diag_a_layers=True)
    assert violations(Plan(factor_sharding="owner"), env) == []
    fitted, dropped = fit_plan(Plan(factor_sharding="owner"), env)
    assert fitted.factor_sharding == "owner" and not dropped
    kfac = KFAC(
        damping=0.01, mesh=data_parallel_mesh(), factor_sharding="owner",
        layers=layers,
    )
    state = kfac.init(params)
    # the vocab-side diag factor lives in a v-group stack, not a matrix
    plan = kfac._shard_plan(*kfac._owner_shapes(
        {"emb": {"A_diag": jnp.ones((16,)), "G": jnp.zeros((8, 8))},
         "fc": {"A": jnp.eye(9), "G": jnp.zeros((4, 4))}}
    ))
    assert plan.diag_group_sizes == (16,)
    assert any(k.startswith("v") for k in state["factor_shard"])


def test_degrade_rules_match_constructor_warnings():
    """Degrade rows (not refusals): the constructor accepts and runs
    inert; fit_plan must clear the same levers so resolved plans never
    carry dead configuration."""
    env = _env(world=1)
    plan = Plan(
        factor_sharding="owner", factor_comm_dtype="bf16", factor_comm_freq=2,
        comm_overlap=True,
    )
    assert not violations(plan, env)  # no refusal...
    fitted, dropped = fit_plan(plan, env)
    assert fitted == Plan()  # ...but nothing survives on one device
    assert set(dropped) == {
        "owner_vs_single_device",
        "comm_vs_single_device",
        "overlap_vs_single_device",
    }
    kfac = KFAC(damping=0.01, **plan.kfac_kwargs())  # warns, constructs
    assert kfac.factor_sharding == "replicated"
    assert kfac.comm_overlap is False


def test_fit_plan_drops_orphaned_staleness_budget():
    """staleness_requires_slack runs LAST: a fit that strips the budget's
    slack (deferral dropped by an earlier rule) must strip the budget too,
    or fit_plan's output would be refused by the constructor it feeds."""
    plan = Plan(factor_comm_freq=4, staleness_budget=2)
    # single device: the degrade rule clears the deferral, orphaning S
    fitted, dropped = fit_plan(plan, _env(world=1))
    assert fitted == Plan()
    assert "comm_vs_single_device" in dropped
    assert "staleness_requires_slack" in dropped
    # multi-axis mesh: the train_step comm rule clears it the same way
    fitted, dropped = fit_plan(plan, _env(world=8, axes=("data", "seq")))
    assert fitted.staleness_budget == 0
    assert "staleness_requires_slack" in dropped
    # ...but chunking slack keeps the budget alive through the same fit
    fitted, dropped = fit_plan(
        dataclasses.replace(plan, eigh_chunks=2),
        _env(world=8, axes=("data", "seq")),
    )
    assert fitted.staleness_budget == 2 and fitted.eigh_chunks == 2
    assert "staleness_requires_slack" not in dropped


# ---------------------------------------------------------------------------
# profile wiring in the constructor
# ---------------------------------------------------------------------------


class _MLP(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(KFACDense(16, name="fc1")(x))
        return KFACDense(10, name="fc2")(x)


def _lowered_text(kfac, **step_kwargs):
    model = _MLP()
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(8, 4, 3).astype(np.float32))
    y = jnp.asarray(r.randint(0, 10, size=8))
    params = model.init(jax.random.PRNGKey(0), x, train=True)["params"]
    tx = make_sgd(momentum=0.9, weight_decay=5e-4)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats={},
        opt_state=tx.init(params),
        kfac_state=kfac.init(params),
    )
    fn = make_train_step(
        model, tx, kfac, train_kwargs={"train": True}, **step_kwargs
    )
    return fn.lower(
        state, (x, y), jnp.float32(0.1), jnp.float32(0.01),
        update_factors=True, update_eigen=True,
    ).as_text()


def test_profile_none_and_safe_are_inert():
    """profile=None and profile="safe" must lower to a program identical
    to today's default construction — the planner costs nothing unless
    levers actually engage."""
    base = _lowered_text(KFAC(damping=0.01))
    assert _lowered_text(KFAC(damping=0.01, profile=None)) == base
    assert _lowered_text(KFAC(damping=0.01, profile="safe")) == base


def test_sgd_hyper_keyword_builds_the_same_step():
    """make_train_step still takes ``sgd_hyper`` (benchmarks/configs/
    transformer_lm.py passes it) and builds the program it builds without."""
    assert _lowered_text(
        KFAC(damping=0.01), sgd_hyper=(0.9, 5e-4)
    ) == _lowered_text(KFAC(damping=0.01))


def test_profile_fills_only_default_levers():
    facts = _BIG_FACTS
    k = KFAC(damping=0.01, profile="production", profile_shapes=facts)
    assert k.solver == "streaming"  # plan filled it
    # explicit non-default lever wins over the plan's choice
    k2 = KFAC(
        damping=0.01, profile="production", profile_shapes=facts,
        solver_rank=64,
    )
    assert k2.solver_rank == 64
    assert k2.plan is not None and k2.plan.solver == "streaming"


def test_profile_accepts_plain_shape_dict():
    k = KFAC(
        damping=0.01, profile="production",
        profile_shapes={f"l{i}": (512, 4608) for i in range(6)},
    )
    assert k.solver == "streaming"


def test_profile_accepts_raw_params_pytree():
    # the constructor must derive facts from a live params tree itself
    # (docs/PLANNER.md promises it) instead of misreading it as a shape dict
    model = _MLP()
    x = jnp.ones((4, 8), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    k = KFAC(
        layers=capture.layer_names(params), damping=0.01,
        profile="production", profile_shapes=params,
    )
    assert k.plan is not None
    facts = model_facts(params, layers=capture.layer_names(params))
    k2 = KFAC(
        layers=capture.layer_names(params), damping=0.01,
        profile="production", profile_shapes=facts,
    )
    assert k.plan == k2.plan


def test_explicit_plan_checked_against_env():
    with pytest.raises(ValueError, match="rsvd_vs_diag_blocks"):
        KFAC(damping=0.01, diag_blocks=2, profile=Plan(solver="rsvd"))
    k = KFAC(damping=0.01, profile=Plan(solver="rsvd", solver_rank=96))
    assert k.solver == "rsvd" and k.solver_rank == 96
    assert k.plan.solver_rank == 96


def test_unknown_profile_refused():
    with pytest.raises(ValueError, match="unknown profile"):
        KFAC(damping=0.01, profile="turbo")


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------


def test_autotune_deterministic_under_fixed_timings():
    env = _env(world=8)
    plan, _, _ = resolve_profile("production", _BIG_FACTS, env)
    cands = candidate_plans(plan, env)
    assert 2 <= len(cands) <= 3
    assert cands[0] == plan and cands[-1] == Plan()

    timings = {c: 1.0 + 0.1 * i for i, c in enumerate(cands)}
    reports = [
        autotune(cands, lambda p, s: timings[p], steps=2) for _ in range(3)
    ]
    assert all(r.winner_index == 0 for r in reports)
    assert all(r.winner == plan for r in reports)
    # ties break toward the earlier candidate (the cost model's pick)
    tied = autotune(cands, lambda p, s: 1.0, steps=2)
    assert tied.winner_index == 0
    # and a faster fallback actually wins
    flipped = autotune(
        cands, lambda p, s: 0.5 if p == Plan() else 1.0, steps=2
    )
    assert flipped.winner == Plan()


def test_candidate_plans_dedupe_to_one_when_safe():
    env = _env(world=1)
    assert candidate_plans(Plan(), env) == [Plan()]


# ---------------------------------------------------------------------------
# plan round-trip through training/checkpoint.py
# ---------------------------------------------------------------------------


def test_plan_round_trips_through_checkpoint(tmp_path):
    from kfac_pytorch_tpu.training import checkpoint as ckpt

    plan, _, _ = resolve_profile(
        "production", _BIG_FACTS, _env(world=32)
    )
    assert plan != Plan()
    payload = {"plan": plan.to_state(), "epoch": np.asarray(3, np.int32)}
    path = ckpt.save_checkpoint(str(tmp_path), 3, payload)
    restored = ckpt.restore_checkpoint(str(tmp_path), 3, payload)
    assert Plan.from_state(restored["plan"]) == plan
    assert path.endswith("checkpoint-3")


def test_plan_dict_round_trip_and_unknown_fields():
    plan = Plan(eigh_chunks=4, solver="rsvd", factor_comm_dtype="bf16")
    assert Plan.from_dict(plan.to_dict()) == plan
    with pytest.raises(ValueError, match="unknown Plan fields"):
        Plan.from_dict({"warp_speed": 9})
    svc = Plan(service_devices=2, staleness_budget=1)
    assert Plan.from_dict(svc.to_dict()) == svc
    assert Plan.from_state(svc.to_state()) == svc
    # pre-service checkpoints lack the field: refresh stays in-step
    legacy = dict(svc.to_state())
    legacy.pop("service_devices")
    assert Plan.from_state(legacy).service_devices == 0


def test_plan_from_state_reads_past_a_stored_apply_kernel():
    """A checkpoint written while Plan still had ``apply_kernel`` (an int32
    index like the other categorical levers) restores to the same plan."""
    plan = Plan(eigh_chunks=2, factor_comm_dtype="bf16", factor_comm_freq=2)
    old = dict(plan.to_state(), apply_kernel=np.asarray(1, np.int32))
    assert Plan.from_state(old) == plan


# ---------------------------------------------------------------------------
# curvature-service engagement (cost model)
# ---------------------------------------------------------------------------


def test_service_engages_only_past_carve_bar():
    """The cost model may spend the operator's carve offer only when the
    dense refresh per interval beats the carved devices' lost capture
    compute by SERVICE_MIN_REFRESH_RATIO — and never invents a carve the
    env didn't offer."""
    from kfac_pytorch_tpu.planner.cost_model import (
        refresh_cost, service_carve_cost,
    )

    # no offer → no service, whatever the shapes
    plan, report, _ = resolve_profile(
        "production", _BIG_FACTS, _env(world=32)
    )
    assert plan.service_devices == 0 and report.service_carve_cost == 0

    # offered + aggressive refresh (K=10): dense refresh clears the bar
    hot = _env(
        world=32, service_devices=2,
        fac_update_freq=1, kfac_update_freq=10,
    )
    plan, report, dropped = resolve_profile("production", _BIG_FACTS, hot)
    assert refresh_cost(_BIG_FACTS, Plan()) > service_carve_cost(
        _BIG_FACTS, hot
    )
    assert plan.service_devices == 2
    assert plan.staleness_budget == 1  # install-slip budget rides along
    # service supersedes the in-step refresh levers...
    assert plan.solver == "eigh"
    assert plan.eigh_chunks == 1
    assert plan.factor_sharding == "replicated"
    # ...without tripping any validity rule on the way out
    assert not dropped
    assert report.service_devices == 2 and report.service_carve_cost > 0

    # offered but lazy refresh (default K=100): amortized in-step refresh
    # is cheaper than the carve — the offer is declined, streaming engages
    cold = _env(world=32, service_devices=2)
    plan, report, _ = resolve_profile("production", _BIG_FACTS, cold)
    assert plan.service_devices == 0
    assert report.service_devices == 0 and report.service_carve_cost > 0


# ---------------------------------------------------------------------------
# expected_step_variants: exact counts, plan arg, autotune budget
# ---------------------------------------------------------------------------


def test_variants_exact_for_composed_plans():
    """The cadence replay counts only programs the schedule can actually
    produce — strictly fewer than the old per-lever worst-case sum for
    composed plans."""
    # chunks=4 at fac 10 / kfac 100: chunk offsets 1..3 never coincide
    # with a factor step, so only chunk 0 gets a ±factors twin:
    # plain, factors, bootstrap, c0±f, c1, c2, c3 → 7 (old bound: 11)
    assert expected_step_variants(
        KFAC(damping=0.01, eigh_chunks=4)
    ) == 7


def test_variants_plan_arg_matches_constructed_kfac():
    mesh = data_parallel_mesh()
    base = KFAC(damping=0.01, mesh=mesh)
    for plan in (
        Plan(),
        Plan(eigh_chunks=3),
        Plan(factor_comm_freq=2),
        Plan(eigh_chunks=3, factor_comm_freq=2),
    ):
        built = KFAC(damping=0.01, mesh=mesh, **plan.kfac_kwargs())
        assert expected_step_variants(base, plan=plan) == (
            expected_step_variants(built)
        ), plan


def test_variants_autotune_budget_term():
    k = KFAC(damping=0.01)
    assert (
        expected_step_variants(k, autotune_candidates=3)
        == expected_step_variants(k) + 6
    )
    assert expected_step_variants(None) == 1
    assert expected_step_variants(None, autotune_candidates=2) == 5
