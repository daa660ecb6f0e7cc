#!/usr/bin/env python
"""Pin the factor-communication fusion in compiled HLO.

The FactorComm plane (parallel/comm.py) replaces the per-layer factor
pmeans — two collectives per K-FAC layer per capture step — with one
collective per flat bucket. This check compiles a mixed conv/dense train
step on the 8-device CPU mesh with the plane active and counts the
``all-reduce`` ops the capture variant adds over the plain variant: that
delta is the factor path's wire cost, and it must stay ≤ the plane's bucket
count. If a change reintroduces per-leaf reductions (or XLA stops fusing
the bucketed ones), the delta jumps to ~2× the layer count and this fails.

Second section: the owner-sharded mode (``factor_sharding="owner"``,
DP-KFAC). Its capture step must contain (a) at most the planned bucket
count of ``reduce-scatter`` ops — the scatter-merge of factor statistics
onto their owners — and (b) EXACTLY ONE ``all-gather``: the preconditioned-
gradient exchange of ``ops.precondition.precondition_all_owner``. The
replicated baseline must contain neither op (its factor exchange is the
bucketed all-reduce pinned above), so a regression that sneaks extra
gathers/scatters into either mode fails loudly.

Third section: the 2-D data×tensor mesh. K-FAC's collectives must ride the
``data`` axis only — under the replicated-compute ``tensor*`` convention the
tensor axis holds identical copies, and a factor collective spanning the
whole mesh would both waste wire and silently average statistics that are
already equal. The pin compiles the owner-sharded capture step for an
embedding+dense LM head on a 4×2 ``data_tensor_mesh`` and asserts (a) the
same rs/ag budget as the 1-D owner pin (≤ planned buckets, exactly one
all-gather — "allgather count unchanged"), and (b) every factor collective's
``replica_groups`` has groups of exactly the DATA world (4), never the full
mesh (8).

Fourth section: compile-only memory regression for the embedding capture.
The token-gather kernel's compiled temp bytes (XLA ``memory_analysis``)
must stay under a tenth of the dense
one-hot oracle's — the [B·T, V] one-hot and dense [V, V] A factor must
never materialize.

Exit 0 with an "OK" line, 1 with a report. Run from the repo root
(tier-1 wraps it in a test, tests/test_scripts.py).
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kfac_pytorch_tpu import platform_override  # noqa: E402

if not platform_override.force_cpu_devices(8):
    print("check_collective_count: SKIP — could not force 8 CPU devices "
          "(backend already initialized)", file=sys.stderr)
    sys.exit(1)

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kfac_pytorch_tpu import KFAC  # noqa: E402
from kfac_pytorch_tpu.models.layers import KFACConv, KFACDense  # noqa: E402
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh  # noqa: E402
from kfac_pytorch_tpu.training.step import (  # noqa: E402
    TrainState,
    make_sgd,
    make_train_step,
)

# matches the op name at an instruction site: "all-reduce(" and
# "all-reduce-start(" (async), but not "all-reduce-done("
_ALLREDUCE_RE = re.compile(r"all-reduce(?:-start)?\(")
_REDUCE_SCATTER_RE = re.compile(r"reduce-scatter(?:-start)?\(")
_ALLGATHER_RE = re.compile(r"all-gather(?:-start)?\(")
# replica_groups in both HLO spellings: literal {{0,2},{1,3}} and iota
# [num_groups,group_size]<=[...] (the V2 form XLA emits for regular grids)
_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{\{([0-9,{} ]*)\}\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _group_sizes(line: str) -> list:
    """Replica-group sizes of one collective instruction line (empty when the
    instruction carries no group list — XLA then means 'all devices')."""
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        return [len(g.split(",")) for g in m.group(1).split("},{") if g]
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return [int(m.group(2))] * int(m.group(1))
    return []


class _Net(nn.Module):
    """Conv + dense mix: several A/G leaves of different shapes, so the
    bucket planner has real fusion work."""

    @nn.compact
    def __call__(self, x, train=True):
        x = nn.relu(KFACConv(8, (3, 3), name="conv1")(x))
        x = nn.relu(KFACConv(8, (3, 3), name="conv2")(x))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(KFACDense(16, name="fc1")(x))
        return KFACDense(10, name="fc2")(x)


def _count_allreduce(hlo: str) -> int:
    return len(_ALLREDUCE_RE.findall(hlo))


def _check_owner(mesh, model, x, y) -> int:
    """Owner-sharded pin: ≤ planned-bucket reduce-scatters on the capture
    step, exactly one preconditioned-gradient all-gather, and a clean
    (no rs/ag) replicated baseline."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tx = make_sgd(momentum=0.9)
    lr, damping = jnp.float32(0.1), jnp.float32(0.01)

    def compile_step(kfac, **flags):
        params = model.init(jax.random.PRNGKey(0), x, train=True)["params"]
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats={},
            opt_state=tx.init(params),
            kfac_state=kfac.init(params),
        )
        # place the state per the mode's contract so the compiled program
        # carries only the mode's own collectives, not resharding noise
        kstate = jax.device_put(
            state.kfac_state, kfac.state_shardings(state.kfac_state)
        )
        state = state.replace(kfac_state=None)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        state = state.replace(kfac_state=kstate)
        batch = tuple(
            jax.device_put(b, NamedSharding(mesh, P("data"))) for b in (x, y)
        )
        step_fn = make_train_step(
            model, tx, kfac, train_kwargs={"train": True},
            mesh=mesh, grad_comm_dtype=jnp.float32,
        )
        lowered = step_fn.lower(state, batch, lr, damping, **flags)
        return lowered.compile().as_text()

    owner = KFAC(damping=0.01, fac_update_freq=1, kfac_update_freq=1,
                 mesh=mesh, factor_sharding="owner")
    repl = KFAC(damping=0.01, fac_update_freq=1, kfac_update_freq=1,
                mesh=mesh)
    own_txt = compile_step(owner, update_factors=True, update_eigen=False)
    rep_txt = compile_step(repl, update_factors=True, update_eigen=False)

    rs = len(_REDUCE_SCATTER_RE.findall(own_txt))
    ag = len(_ALLGATHER_RE.findall(own_txt))
    rs_rep = len(_REDUCE_SCATTER_RE.findall(rep_txt))
    ag_rep = len(_ALLGATHER_RE.findall(rep_txt))
    buckets = owner.factor_comm.last_collectives or 0
    print(
        f"check_collective_count: owner capture step {rs} reduce-scatter(s) "
        f"vs {buckets} planned bucket(s), {ag} all-gather(s); replicated "
        f"baseline {rs_rep} reduce-scatter(s), {ag_rep} all-gather(s)"
    )
    if buckets < 1:
        print("check_collective_count: FAIL — owner capture trace never "
              "planned scatter buckets", file=sys.stderr)
        return 1
    if rs > buckets:
        print(
            f"check_collective_count: FAIL — owner capture step has {rs} "
            f"reduce-scatters but the plan allows only {buckets} bucket(s); "
            "the scatter-merge has unfused", file=sys.stderr,
        )
        return 1
    if ag != 1:
        print(
            f"check_collective_count: FAIL — owner capture step has {ag} "
            "all-gathers; the mode's contract is exactly ONE (the "
            "preconditioned-gradient exchange)", file=sys.stderr,
        )
        return 1
    if rs_rep != 0 or ag_rep != 0:
        print(
            f"check_collective_count: FAIL — replicated baseline grew "
            f"{rs_rep} reduce-scatter(s) / {ag_rep} all-gather(s); the "
            "default mode must not issue owner-path collectives",
            file=sys.stderr,
        )
        return 1
    print("check_collective_count: OK — owner mode pinned to "
          f"≤ {buckets} reduce-scatter(s) + 1 all-gather")
    return 0


class _LMHead(nn.Module):
    """Embedding + dense head: one diagonal-A layer and one matrix layer, so
    the 2-D pin covers both the v-group scatter and the matrix buckets."""

    @nn.compact
    def __call__(self, ids, train=True):
        from kfac_pytorch_tpu.models.layers import KFACEmbed

        x = KFACEmbed(32, 16, name="emb")(ids)
        x = jnp.mean(x, axis=1)
        return KFACDense(10, name="fc")(x)


def _check_2d_mesh() -> int:
    """data×tensor pin: owner-sharded K-FAC on a 4×2 mesh keeps the 1-D
    collective budget AND every factor collective stays inside a data-axis
    replica group (size 4), never spanning the full 8-device mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kfac_pytorch_tpu.parallel.mesh import data_tensor_mesh

    mesh = data_tensor_mesh(2)
    data_world = mesh.shape["data"]
    model = _LMHead()
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 32, size=(16, 12)).astype(np.int32))
    y = jnp.asarray(r.randint(0, 10, size=16))
    tx = make_sgd(momentum=0.9)
    lr, damping = jnp.float32(0.1), jnp.float32(0.01)

    kfac = KFAC(damping=0.01, fac_update_freq=1, kfac_update_freq=1,
                mesh=mesh, factor_sharding="owner",
                factor_comm_dtype="bf16", factor_comm_freq=1)
    params = model.init(jax.random.PRNGKey(0), ids, train=True)["params"]
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats={},
        opt_state=tx.init(params),
        kfac_state=kfac.init(params),
    )
    kstate = jax.device_put(
        state.kfac_state, kfac.state_shardings(state.kfac_state)
    )
    state = state.replace(kfac_state=None)
    state = jax.device_put(state, NamedSharding(mesh, P()))
    state = state.replace(kfac_state=kstate)
    batch = tuple(
        jax.device_put(b, NamedSharding(mesh, P("data"))) for b in (ids, y)
    )
    step_fn = make_train_step(
        model, tx, kfac, train_kwargs={"train": True},
        mesh=mesh, grad_comm_dtype=jnp.float32,
    )
    hlo = step_fn.lower(
        state, batch, lr, damping, update_factors=True, update_eigen=False
    ).compile().as_text()

    rs_lines = [ln for ln in hlo.splitlines() if _REDUCE_SCATTER_RE.search(ln)]
    ag_lines = [ln for ln in hlo.splitlines() if _ALLGATHER_RE.search(ln)]
    buckets = kfac.factor_comm.last_collectives or 0
    print(
        f"check_collective_count: 2-D mesh ({mesh.shape}) owner capture step "
        f"{len(rs_lines)} reduce-scatter(s) vs {buckets} planned bucket(s), "
        f"{len(ag_lines)} all-gather(s)"
    )
    if buckets < 1:
        print("check_collective_count: FAIL — 2-D owner capture trace never "
              "planned scatter buckets", file=sys.stderr)
        return 1
    if len(rs_lines) > buckets:
        print(
            f"check_collective_count: FAIL — 2-D mesh capture step has "
            f"{len(rs_lines)} reduce-scatters vs {buckets} planned bucket(s); "
            "the scatter-merge has unfused under the tensor axis",
            file=sys.stderr,
        )
        return 1
    if len(ag_lines) != 1:
        print(
            f"check_collective_count: FAIL — 2-D mesh capture step has "
            f"{len(ag_lines)} all-gathers; the owner contract (exactly ONE "
            "preconditioned-gradient exchange) must not change with the "
            "tensor axis", file=sys.stderr,
        )
        return 1
    for ln in rs_lines + ag_lines:
        sizes = _group_sizes(ln)
        if not sizes:
            print(
                "check_collective_count: FAIL — 2-D mesh factor collective "
                "carries no replica_groups (spans the whole mesh):\n  "
                + ln.strip()[:200], file=sys.stderr,
            )
            return 1
        if any(s != data_world for s in sizes):
            print(
                f"check_collective_count: FAIL — 2-D mesh factor collective "
                f"replica groups {sizes} != data world {data_world}; a "
                "factor collective escaped the data axis:\n  "
                + ln.strip()[:200], file=sys.stderr,
            )
            return 1
    print(
        "check_collective_count: OK — 2-D mesh factor collectives confined "
        f"to data-axis groups of {data_world}, all-gather count unchanged"
    )
    return 0


class _ShardNet(nn.Module):
    """Column + row sharded kernels plus one replicated dense layer — the
    three factor families of the 3-D pin."""

    @nn.compact
    def __call__(self, x, train=True):
        from kfac_pytorch_tpu.models.layers import KFACShardedDense

        h = nn.gelu(
            KFACShardedDense(16, 2, sharding="column", name="col")(x)
        )
        h = KFACShardedDense(
            12, 2, sharding="row", use_bias=False, name="row"
        )(h)
        return KFACDense(10, name="fc")(h)


def _check_3d_mesh() -> int:
    """3-D data×fsdp×tensor pin (docs/SHARDING.md): with params placed via
    shardwise.lm_param_shardings and factors via KFAC.state_shardings, the
    factor capture path must add collectives ONLY in joint data×fsdp
    replica groups (size data_world·fsdp_world). Zero tensor-axis
    additions: the column-sharded G stack is captured and preconditioned
    shard-locally, the row-sharded A slices are local to their shard, and
    the row output-grad psum is the forward matmul's own reduction —
    present in the plain variant too, so the capture delta on the tensor
    axis is exactly the predicted per-shard psum set: empty."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kfac_pytorch_tpu import capture, shardwise
    from kfac_pytorch_tpu.parallel.mesh import data_fsdp_tensor_mesh

    mesh = data_fsdp_tensor_mesh(2, 2)
    factor_world = mesh.shape["data"] * mesh.shape["fsdp"]
    model = _ShardNet()
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(16, 8).astype(np.float32))
    y = jnp.asarray(r.randint(0, 10, size=16))
    layers = capture.discover_layers(model, x, train=True)
    kfac = KFAC(damping=0.01, fac_update_freq=1, kfac_update_freq=1,
                mesh=mesh, layers=layers)
    tx = make_sgd(momentum=0.9)
    params = model.init(jax.random.PRNGKey(0), x, train=True)["params"]
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats={},
        opt_state=tx.init(params),
        kfac_state=kfac.init(params),
    )
    pshard = shardwise.lm_param_shardings(params, layers, mesh)
    kstate = jax.device_put(
        state.kfac_state, kfac.state_shardings(state.kfac_state)
    )
    state = state.replace(params=None, kfac_state=None)
    state = jax.device_put(state, NamedSharding(mesh, P()))
    state = state.replace(
        params=jax.device_put(params, pshard), kfac_state=kstate
    )
    batch = tuple(
        jax.device_put(b, NamedSharding(mesh, P(("data", "fsdp"))))
        for b in (x, y)
    )
    step_fn = make_train_step(model, tx, kfac, train_kwargs={"train": True})
    lr, damping = jnp.float32(0.1), jnp.float32(0.01)

    def hist(**flags):
        """(op, replica-group size) → instruction count."""
        hlo = step_fn.lower(
            state, batch, lr, damping, **flags
        ).compile().as_text()
        out = {}
        for op, rx in (
            ("all-reduce", _ALLREDUCE_RE),
            ("reduce-scatter", _REDUCE_SCATTER_RE),
            ("all-gather", _ALLGATHER_RE),
        ):
            for ln in hlo.splitlines():
                if rx.search(ln):
                    sizes = _group_sizes(ln) or [mesh.size]
                    out[(op, sizes[0])] = out.get((op, sizes[0]), 0) + 1
        return out

    plain = hist(update_factors=False, update_eigen=False)
    cap = hist(update_factors=True, update_eigen=False)
    delta = {
        k: cap.get(k, 0) - plain.get(k, 0) for k in set(cap) | set(plain)
    }
    off_axis = {
        f"{op}@{size}": n for (op, size), n in sorted(delta.items())
        if n > 0 and (op, size) != ("all-reduce", factor_world)
    }
    added = delta.get(("all-reduce", factor_world), 0)
    print(
        f"check_collective_count: 3-D mesh ({dict(mesh.shape)}) capture "
        f"delta {added} all-reduce(s) in data×fsdp groups of {factor_world}; "
        f"off-axis additions: {off_axis or 'none'}"
    )
    if off_axis:
        print(
            "check_collective_count: FAIL — the 3-D factor path added "
            f"collectives outside the data×fsdp replica groups: {off_axis}. "
            "The tensor axis must stay capture-collective-free (per-shard "
            "G/A blocks live where their kernel shard lives)",
            file=sys.stderr,
        )
        return 1
    if cap.get(("all-reduce", factor_world), 0) < 1:
        print(
            "check_collective_count: FAIL — 3-D capture step carries no "
            f"all-reduce in data×fsdp groups of {factor_world}; the factor "
            "statistics are not being exchanged across replicas",
            file=sys.stderr,
        )
        return 1
    print(
        "check_collective_count: OK — 3-D mesh factor exchange confined to "
        f"data×fsdp groups of {factor_world}, zero tensor-axis additions"
    )
    return 0


def _check_embed_memory() -> int:
    """Compile-only memory pin: the token-gather embedding capture must not
    materialize the one-hot program — temp bytes < dense oracle / 10."""
    from kfac_pytorch_tpu.ops import factor_kernels, factors

    def _compiled_memory(lowered):
        # memory_analysis() is best-effort per backend: a failure is an
        # error note, read below as a skip
        try:
            stats = lowered.compile().memory_analysis()
            return {"temp_bytes": int(stats.temp_size_in_bytes)}
        except Exception as e:  # noqa: BLE001 — backend-dependent reporting
            return {"error": f"{type(e).__name__}: {e}"[:200]}

    vocab, toks = 4096, (16, 512)  # one-hot temp: 16·512·4096·4 B = 128 MiB
    ids = jnp.zeros(toks, jnp.int32)
    fused = _compiled_memory(
        jax.jit(lambda i: factor_kernels.compute_a_embed_fused(i, vocab))
        .lower(ids)
    )
    dense = _compiled_memory(
        jax.jit(lambda i: factors.compute_a_embed_onehot(i, vocab)).lower(ids)
    )
    if "temp_bytes" not in fused or "temp_bytes" not in dense:
        # memory_analysis is best-effort per backend; absence is a skip, not
        # a regression (the TPU path reports it)
        print(
            "check_collective_count: OK — embedding memory pin skipped "
            f"(memory_analysis unavailable: {fused.get('error') or dense.get('error')})"
        )
        return 0
    print(
        f"check_collective_count: embedding capture temp bytes "
        f"{fused['temp_bytes']} (token-gather) vs {dense['temp_bytes']} "
        "(dense one-hot oracle)"
    )
    if fused["temp_bytes"] * 10 >= dense["temp_bytes"]:
        print(
            "check_collective_count: FAIL — the token-gather capture's temp "
            f"bytes ({fused['temp_bytes']}) are not under a tenth of the "
            f"dense one-hot oracle's ({dense['temp_bytes']}); the [B·T, V] "
            "one-hot is materializing again", file=sys.stderr,
        )
        return 1
    print(
        "check_collective_count: OK — embedding capture stays "
        f"{dense['temp_bytes'] // max(fused['temp_bytes'], 1)}× under the "
        "one-hot footprint"
    )
    return 0


def main() -> int:
    mesh = data_parallel_mesh()
    model = _Net()
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(16, 8, 8, 3).astype(np.float32))
    y = jnp.asarray(r.randint(0, 10, size=16))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    tx = make_sgd(momentum=0.9)
    params = variables["params"]
    # bf16 wire activates the plane (and the explicit-collective wrapper)
    # at comm_freq=1, so the capture variant carries the bucketed exchange
    kfac = KFAC(damping=0.01, fac_update_freq=1, kfac_update_freq=1,
                mesh=mesh, factor_comm_dtype="bf16")
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(params),
        kfac_state=kfac.init(params),
    )
    step_fn = make_train_step(model, tx, kfac, train_kwargs={"train": True})
    lr, damping = jnp.float32(0.1), jnp.float32(0.01)

    def hlo(**flags):
        lowered = step_fn.lower(state, (x, y), lr, damping, **flags)
        return lowered.compile().as_text()

    plain = _count_allreduce(hlo(update_factors=False, update_eigen=False))
    captured = _count_allreduce(hlo(update_factors=True, update_eigen=False))
    buckets = kfac.factor_comm.last_collectives
    if buckets is None:
        print("check_collective_count: FAIL — the capture trace never "
              "planned factor buckets (plane inactive?)", file=sys.stderr)
        return 1

    delta = captured - plain
    print(
        f"check_collective_count: plain step {plain} all-reduce(s), capture "
        f"step {captured}; factor-path delta {delta} vs {buckets} planned "
        f"bucket(s) [{kfac.factor_comm.last_wire_bytes} wire bytes]"
    )
    if delta > buckets:
        print(
            f"check_collective_count: FAIL — the capture variant adds "
            f"{delta} all-reduces but the plane planned only {buckets} "
            "bucket(s); the factor exchange has unfused into per-leaf "
            "collectives", file=sys.stderr,
        )
        return 1
    print(f"check_collective_count: OK — factor exchange fused into "
          f"≤ {buckets} bucketed all-reduce(s)")
    rc = _check_owner(mesh, model, x, y)
    if rc:
        return rc
    rc = _check_2d_mesh()
    if rc:
        return rc
    rc = _check_3d_mesh()
    if rc:
        return rc
    return _check_embed_memory()


if __name__ == "__main__":
    sys.exit(main())
