"""Owner-sharded factor state (``KFAC(factor_sharding="owner")``, DP-KFAC).

Pins the mode's three contracts on the 8-device CPU mesh:

* **parity** — owner == replicated at rtol 1e-6 over ≥2 eigen-refresh
  intervals, composed (each lever separately — chunks×defer would read
  different mid-window factor snapshots by design) with ``eigh_chunks>1``,
  ``factor_comm_freq>1``, and ``solver="rsvd"``; the EMA is linear in its
  contributions, so the reduce-scattered owner EMA equals the replicated
  one up to reassociation;
* **memory** — the per-replica factor+eigen footprint in owner mode is
  less than half the replicated footprint (the whole point of the layout);
* **inertness** — the default ``"replicated"`` mode compiles an HLO-
  identical program to an explicit pre-flag-style construction, and
  unsupported compositions refuse loudly at construction instead of
  silently degrading (except 1-device meshes, which warn and degrade —
  there is nothing to shard across).

The HLO collective pin (≤ bucket-count reduce-scatters + exactly one
all-gather) lives in scripts/check_collective_count.py (tier-1 via
tests/test_scripts.py); the checkpoint round-trip/migration contracts in
tests/test_checkpoint.py.
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu import KFAC
from kfac_pytorch_tpu.compile_cache import expected_step_variants
from kfac_pytorch_tpu.models.layers import KFACDense
from kfac_pytorch_tpu.parallel.assignment import (
    plan_factor_shards,
    shard_plan_bytes,
)
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh, data_tensor_mesh
from kfac_pytorch_tpu.training.step import (
    TrainState,
    kfac_flags_for_step,
    make_sgd,
    make_train_step,
)


class _MLP(nn.Module):
    """Three dense layers → two factor sizes (33/25-ish A, 32/10 G): the
    LPT plan spreads owners and the shape-group stacks have >1 row."""

    @nn.compact
    def __call__(self, x, train=True):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(KFACDense(32, name="fc1")(x))
        x = nn.relu(KFACDense(32, name="fc2")(x))
        return KFACDense(10, name="fc3")(x)


def _setup(model, kfac, mesh, batch=16, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(batch, 4, 6).astype(np.float32))
    y = jnp.asarray(r.randint(0, 10, size=batch))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    tx = make_sgd(momentum=0.9, weight_decay=5e-4)
    params = variables["params"]
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(params),
        kfac_state=kfac.init(params),
    )
    step_fn = make_train_step(model, tx, kfac, train_kwargs={"train": True},
                              mesh=mesh, grad_comm_dtype=jnp.float32)
    return state, step_fn, (x, y)


def _put(state, batch, mesh, kfac):
    """Owner-aware placement: curvature shards per state_shardings, the
    rest replicated (replicated-mode states place blanket-replicated)."""
    bshard = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    if kfac.owner_sharded:
        kstate = jax.device_put(state.kfac_state,
                                kfac.state_shardings(state.kfac_state))
        state = state.replace(kfac_state=None)
        state = jax.device_put(state, repl)
        state = state.replace(kfac_state=kstate)
    else:
        state = jax.device_put(state, repl)
    return state, tuple(jax.device_put(b, bshard) for b in batch)


def _run(kw_extra, steps=7, mesh=None):
    """steps=7 at kfac_update_freq=3 crosses two refresh boundaries (steps
    3 and 6), so parity covers capture, refresh, and post-refresh
    preconditioning in both EMA regimes."""
    if mesh is None:
        mesh = data_parallel_mesh()
    kw = dict(damping=0.01, fac_update_freq=1, kfac_update_freq=3, mesh=mesh)
    kw.update(kw_extra)
    kfac = KFAC(**kw)
    state, fn, batch = _setup(_MLP(), kfac, mesh)
    state, b = _put(state, batch, mesh, kfac)
    for step in range(steps):
        fl = kfac_flags_for_step(step, kfac)
        state, _ = fn(state, b, jnp.float32(0.05), jnp.float32(0.01), **fl)
    return state, kfac


def _assert_close(pa, pb, rtol=1e-6, atol=1e-7):
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(pa)),
        jax.tree_util.tree_leaves(jax.device_get(pb)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


# --------------------------------------------------------------- parity


@pytest.mark.parametrize(
    "extra",
    [
        pytest.param({}, id="base"),
        pytest.param({"eigh_chunks": 2}, id="eigh_chunks"),
        pytest.param({"factor_comm_freq": 2}, id="comm_freq"),
        pytest.param(
            {"solver": "rsvd", "solver_auto_threshold": 16, "solver_rank": 8},
            id="rsvd",
        ),
    ],
)
def test_owner_matches_replicated(extra):
    s_rep, _ = _run(dict(extra))
    s_own, _ = _run({**extra, "factor_sharding": "owner"})
    _assert_close(s_rep.params, s_own.params)


@pytest.mark.parametrize(
    "extra",
    [
        pytest.param({}, id="base"),
        pytest.param({"eigh_chunks": 2}, id="eigh_chunks"),
        pytest.param({"factor_comm_freq": 2}, id="comm_freq"),
        pytest.param(
            {"solver": "rsvd", "solver_auto_threshold": 16, "solver_rank": 8},
            id="rsvd",
        ),
        pytest.param({"factor_sharding": "owner"}, id="owner"),
    ],
)
def test_2d_mesh_matches_1d_mesh(extra):
    """Lifting the pure-DP guard: on a 4×2 data×tensor mesh (the tensor
    axis carries replicated compute) every K-FAC lever must land the same
    parameters as the plain 8-device DP mesh — the global batch statistics
    are identical, only the collective replica groups change (owner shards
    size to factor_world=4 instead of 8, the EMA is linear, so parity
    holds up to reassociation)."""
    s_1d, _ = _run(dict(extra))
    s_2d, _ = _run(dict(extra), mesh=data_tensor_mesh(2))
    _assert_close(s_1d.params, s_2d.params)


# --------------------------------------------------------------- memory


class _DeepMLP(nn.Module):
    """16 K-FAC layers: enough slots that the 8-way owner division beats
    the per-size padding rows (with ~1 slot/device, padding would eat the
    savings — the layout targets real nets, not 3-layer toys)."""

    @nn.compact
    def __call__(self, x, train=True):
        x = x.reshape((x.shape[0], -1))
        for i in range(15):
            x = nn.relu(KFACDense(32, name=f"fc{i}")(x))
        return KFACDense(10, name="head")(x)


def test_owner_halves_per_replica_factor_memory():
    """The acceptance bar: per-replica factor+eigen bytes in owner mode
    < replicated/2 on the 8-device mesh, measured on the REAL states."""
    mesh = data_parallel_mesh()
    world = mesh.devices.size

    def bytes_local(kfac):
        state = kfac.init(
            _DeepMLP().init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 4, 6)), train=True)["params"]
        )
        sharded = ("factor_shard", "eigen_shard", "eigen_pending_shard")
        return sum(
            leaf.nbytes // (world if key in sharded else 1)
            for key in ("factors", "eigen", "eigen_stacked") + sharded
            for leaf in jax.tree_util.tree_leaves(state.get(key, {}))
        )

    repl = bytes_local(KFAC(damping=0.01, mesh=mesh))
    own = bytes_local(KFAC(damping=0.01, mesh=mesh, factor_sharding="owner"))
    assert own < repl / 2, (own, repl)


def test_shard_plan_bytes_model():
    """shard_plan_bytes prices the same layout the gauges report: local
    buffers shrink ~world-fold vs the replicated total (padding rows cost
    the difference), and every byte count is positive and consistent."""
    shapes = {f"fc{i}": (32, 33) for i in range(15)}
    shapes["head"] = (10, 33)
    plan = plan_factor_shards(shapes, world=8)
    info = shard_plan_bytes(plan)
    assert info["owner_count"] == plan.owner_count()
    assert 0 < info["total_buffer_local"] < info["replicated_total"] / 2
    assert info["total_buffer_local"] == (
        info["factor_buffer_local"] + info["eigen_buffer_local"]
    )
    assert info["wire_bucket_count"] >= 1
    assert info["scatter_wire_bytes"] > 0


def test_shard_plan_deterministic():
    shapes = {"fc1": (32, 25), "fc2": (32, 33), "fc3": (10, 33)}
    a = plan_factor_shards(shapes, world=8)
    b = plan_factor_shards(dict(reversed(list(shapes.items()))), world=8)
    assert a.slots == b.slots
    assert a.group_rows == b.group_rows
    # every (name, factor) appears exactly once, on a valid device
    seen = {(s.name, s.factor) for s in a.slots}
    assert len(seen) == len(a.slots) == 2 * len(shapes)
    assert all(0 <= s.owner < 8 for s in a.slots)


# ------------------------------------------------------------- inertness


def _without_source_locations(hlo: str) -> str:
    """Compiled HLO text minus where in the Python source each op came from:
    the installed JAX prints a FileNames/FunctionNames/FileLocations/
    StackFrames table and per-op ``stack_frame_id``s, so two identical
    programs lowered from adjacent source lines differ in text only."""
    body = hlo.split("\n%", 1)
    header = body[0].split("\n\nFileNames\n", 1)[0]
    hlo = header + "\n%" + body[1]
    return re.sub(r" stack_frame_id=\d+", "", hlo)


def test_default_replicated_hlo_identical():
    """KFAC() and KFAC(factor_sharding="replicated") must compile the SAME
    capture-step program — the flag's default is inert down to the HLO
    (compared with source-location metadata stripped)."""
    mesh = data_parallel_mesh()
    model = _MLP()

    def compiled(kfac):
        state, fn, batch = _setup(model, kfac, mesh)
        state, b = _put(state, batch, mesh, kfac)
        return fn.lower(
            state, b, jnp.float32(0.05), jnp.float32(0.01),
            update_factors=True, update_eigen=False,
        ).compile().as_text()

    kw = dict(damping=0.01, fac_update_freq=1, kfac_update_freq=3, mesh=mesh)
    default_txt = _without_source_locations(compiled(KFAC(**kw)))
    explicit_txt = _without_source_locations(
        compiled(KFAC(**kw, factor_sharding="replicated"))
    )
    assert "FileLocations" not in default_txt and "ENTRY" in default_txt
    assert default_txt == explicit_txt
    assert "reduce-scatter" not in default_txt
    assert "all-gather" not in default_txt


def test_owner_adds_no_step_variants():
    mesh = data_parallel_mesh()
    kw = dict(damping=0.01, mesh=mesh)
    assert expected_step_variants(
        KFAC(**kw, factor_sharding="owner")
    ) == expected_step_variants(KFAC(**kw))


@pytest.mark.parametrize(
    "kw, msg",
    [
        (dict(precond_method="inverse"), "precond_method"),
        (dict(diag_blocks=2), "diag_blocks"),
        (dict(distribute_precondition=True), "distribute_precondition"),
        (dict(track_diagnostics=True), "diagnostics"),
        (dict(factor_sharding="banana"), "factor_sharding"),
    ],
)
def test_owner_refuses_unsupported_compositions(kw, msg):
    mesh = data_parallel_mesh()
    sharding = kw.pop("factor_sharding", "owner")
    with pytest.raises(ValueError, match=msg):
        KFAC(damping=0.01, mesh=mesh, factor_sharding=sharding, **kw)


def test_owner_refuses_multi_axis_mesh():
    """A real second axis (sequence/model parallel) still refuses — only
    replicated-compute 'tensor*' axes ride along (data_tensor_mesh)."""
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices()).reshape(4, 2)
    mesh = Mesh(devices, ("data", "seq"))
    with pytest.raises(ValueError, match="data-plane"):
        KFAC(damping=0.01, mesh=mesh, factor_sharding="owner")
    # the exempt spelling constructs and owner-shards over the data axis
    assert KFAC(
        damping=0.01, mesh=Mesh(devices, ("data", "tensor")),
        factor_sharding="owner",
    ).owner_sharded


def test_owner_degrades_on_single_device(capsys):
    """1-device meshes warn and fall back to the replicated layout — the
    same degrade contract as distribute_precondition, so trainers can pass
    identical flags to dev runs."""
    kfac = KFAC(damping=0.01, factor_sharding="owner")
    assert not kfac.owner_sharded
    assert kfac.factor_sharding == "replicated"
    assert "WARNING" in capsys.readouterr().out


def test_owner_shapes_diag_a_layers():
    """Diagonal-A (embedding) factors shard as [vocab] vector slots: the
    shape map reports (features, vocab) and the layer lands in the diag set
    (the PR-6 refusal replaced by the real v-group rule)."""
    mesh = data_parallel_mesh()
    kfac = KFAC(damping=0.01, mesh=mesh, factor_sharding="owner")
    shapes, diag = kfac._owner_shapes(
        {
            "emb": {
                "A_diag": jnp.ones((32,)),
                "G": jnp.zeros((4, 4)),
            },
            "dense": {"A": jnp.eye(5), "G": jnp.zeros((4, 4))},
        }
    )
    assert shapes == {"emb": (4, 32), "dense": (4, 5)}
    assert diag == {"emb"}
    plan = kfac._shard_plan(shapes, frozenset(diag))
    assert plan.diag_group_sizes == (32,)
    slot = plan.slot("emb", "A")
    assert slot.diag and slot.size == 32
    assert not plan.slot("emb", "G").diag
    assert not plan.slot("dense", "A").diag
