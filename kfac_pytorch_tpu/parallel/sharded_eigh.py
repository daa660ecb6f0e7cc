"""SPMD-sharded, shape-bucketed factor eigendecomposition over a device mesh.

The reference distributes per-layer eigendecompositions across Horovod ranks:
owners compute, non-owners zero their buffers, and a Sum-allreduce reassembles
("allgather via sum of zeros", kfac_preconditioner.py:196-255, 421-437).

The TPU-native version keeps that communication pattern but re-plans the
compute for XLA's compilation model. Every (layer, factor, diag-block) job is
a *slot* with a static owner device (parallel/assignment.py). Slots are
rounded up to a small set of padded shape buckets (ops/eigh.py — TPU eigh
compile cost is per-distinct-shape and brutal above n≈1024), and inside ONE
``shard_map`` program each device:

1. gathers the padded blocks for the slots it owns into a uniform
   ``[rows, m, m]`` stack (a static per-device index table, so the gather is
   just ``jnp.take`` on a replicated stack),
2. runs one batched eigh per bucket,
3. scatter-adds its results into a zeroed all-slots buffer, and
4. a single ``psum`` per bucket reassembles every device's slots — the
   reference's exact sum-of-zeros exchange, riding ICI.

Per-device eigh work shrinks ~1/world while the number of compiled eigh
shapes stays at the bucket count (≤ ~6 for ResNet-50) regardless of world
size or layer count.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kfac_pytorch_tpu import compat
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.ops.eigh import (
    batched_eigh,
    bucket_size,
    get_block_boundary,
    pad_for_eigh,
    symmetrize,
    unpad_eigh,
)
from kfac_pytorch_tpu.ops.rsvd import (
    batched_randomized_eigh,
    pad_for_rsvd,
    residual_rho,
)

Assignment = Dict[str, Dict[str, Tuple[int, ...]]]


# A slot's refresh result: dense slots yield (Q [n, n], d [n]); slots the
# randomized solver truncates yield (Q_r [n, r], d_r [r], rho). Tuple arity
# is the discriminator throughout this module.


def _split_by_rank(
    slots: List[EighSlot], rank_fn
) -> Tuple[List[int], Dict[int, List[int]]]:
    """Partition slot indices into (dense, {rank: [indices]}) per ``rank_fn``.

    ``rank_fn(size) -> Optional[int]`` is the single size→rank policy (the
    preconditioner's solver_rank/solver_auto_threshold rule); ``None`` for a
    size means the dense eigh keeps that slot. Shared by every update path so
    the replicated, sharded, monolithic, and chunked variants truncate the
    exact same slot set.
    """
    dense: List[int] = []
    by_rank: Dict[int, List[int]] = {}
    for i, s in enumerate(slots):
        r = rank_fn(s.size) if rank_fn is not None else None
        if r is None:
            dense.append(i)
        else:
            by_rank.setdefault(int(r), []).append(i)
    return dense, by_rank


@dataclasses.dataclass(frozen=True)
class EighSlot:
    """One eigendecomposition job: a diagonal block of one layer's factor."""

    name: str
    factor: str  # 'A' | 'G'
    start: int  # block row range within the factor
    stop: int
    owner: int  # owning device index along the mesh axis

    @property
    def size(self) -> int:
        return self.stop - self.start


def build_slots(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    assignment: Optional[Assignment],
    blocks_per_layer: Optional[Dict[str, int]] = None,
) -> List[EighSlot]:
    """Expand factors into per-block jobs with owners.

    With an ``assignment`` table, block count and owners come from the ranks
    tuples (block count capped at ``min(shape)`` exactly as
    kfac_preconditioner.py:244-247). Without one (replicated mode),
    ``blocks_per_layer`` gives the counts and device 0 owns everything.
    """
    slots: List[EighSlot] = []
    for name in factors:
        for fac in ("A", "G"):
            if fac not in factors[name]:
                continue  # diagonal-A (embedding) layers have no A matrix
            n = factors[name][fac].shape[0]
            if assignment is not None:
                owners = assignment[name][fac]
            else:
                owners = (0,) * (blocks_per_layer or {}).get(name, 1)
            nb = min(len(owners), n)
            for b in range(nb):
                (r0, _), (r1, _) = get_block_boundary(b, nb, (n, n))
                slots.append(EighSlot(name, fac, r0, r1, owners[b]))
    return slots


def _bucket_groups(
    slots: List[EighSlot], granularity: int, minimum: int
) -> Dict[int, List[int]]:
    groups: Dict[int, List[int]] = {}
    for i, s in enumerate(slots):
        groups.setdefault(bucket_size(s.size, granularity, minimum), []).append(i)
    return dict(sorted(groups.items()))


def _padded_stack(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    slots: List[EighSlot],
    idxs: List[int],
    m: int,
) -> jnp.ndarray:
    rows = []
    for i in idxs:
        s = slots[i]
        f = factors[s.name][s.factor]
        blk = f[s.start : s.stop, s.start : s.stop].astype(jnp.float32)
        rows.append(pad_for_eigh(symmetrize(blk), m))
    return jnp.stack(rows)


def _rsvd_stack(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    slots: List[EighSlot],
    idxs: List[int],
    m: int,
) -> jnp.ndarray:
    """Zero-padded bucket stack for the randomized solver (pad_for_rsvd —
    the −1 pad diagonal of the dense path would dominate the power
    iteration on small PSD spectra)."""
    rows = []
    for i in idxs:
        s = slots[i]
        f = factors[s.name][s.factor]
        blk = f[s.start : s.stop, s.start : s.stop].astype(jnp.float32)
        rows.append(pad_for_rsvd(symmetrize(blk), m))
    return jnp.stack(rows)


def _rank_groups(
    slots: List[EighSlot],
    rank_fn,
    granularity: int,
    minimum: int,
) -> Tuple[Dict[int, List[int]], Dict[Tuple[int, int], List[int]]]:
    """Split slots into dense bucket groups and ``(bucket, rank)`` rsvd
    groups, both carrying GLOBAL slot indices. With ``rank_fn=None`` the
    dense groups equal :func:`_bucket_groups` exactly (bitwise-inert)."""
    dense_idx, by_rank = _split_by_rank(slots, rank_fn)
    groups: Dict[int, List[int]] = {}
    for i in dense_idx:
        groups.setdefault(
            bucket_size(slots[i].size, granularity, minimum), []
        ).append(i)
    lr_groups: Dict[Tuple[int, int], List[int]] = {}
    for r, idxs in sorted(by_rank.items()):
        for i in idxs:
            lr_groups.setdefault(
                (bucket_size(slots[i].size, granularity, minimum), r), []
            ).append(i)
    return dict(sorted(groups.items())), dict(sorted(lr_groups.items()))


def _owner_tables(
    slots: List[EighSlot], idxs: List[int], world: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-device (row indices, validity mask) tables for one bucket group:
    device ``dev`` owns stack rows ``idx_tab[dev][:count]``; rows past its
    count point at row 0 and are masked out by ``valid``."""
    owned = [
        [r for r, i in enumerate(idxs) if slots[i].owner == dev]
        for dev in range(world)
    ]
    rows = max(1, max(len(o) for o in owned))
    idx_tab = [(o + [0] * (rows - len(o))) for o in owned]
    valid = [[1.0] * len(o) + [0.0] * (rows - len(o)) for o in owned]
    return jnp.asarray(idx_tab, jnp.int32), jnp.asarray(valid, jnp.float32)


def _assemble(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    slots: List[EighSlot],
    results: Dict[int, Tuple[jnp.ndarray, ...]],
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Scatter per-slot results into per-layer eigen buffers.

    Dense ``(Q, d)`` results scatter into zeroed block-diagonal buffers; a
    truncated ``(Q_r, d_r, rho)`` result IS its factor's whole eigen entry
    (the randomized solver is excluded from ``diag_blocks > 1``, so a
    truncated slot always spans its full factor) and is stored rectangular
    plus the scalar residual mass — no zero buffer ever materializes for it.
    """
    lr_pairs = {
        (s.name, s.factor) for i, s in enumerate(slots) if len(results[i]) == 3
    }
    eigen: Dict[str, Dict[str, jnp.ndarray]] = {}
    for name, f in factors.items():
        eigen[name] = {}
        for fac, qk, dk in (("A", "QA", "dA"), ("G", "QG", "dG")):
            if fac in f and (name, fac) not in lr_pairs:
                n = f[fac].shape[0]
                eigen[name][qk] = jnp.zeros((n, n), jnp.float32)
                eigen[name][dk] = jnp.zeros((n,), jnp.float32)
    for i, s in enumerate(slots):
        res = results[i]
        qk, dk = ("QA", "dA") if s.factor == "A" else ("QG", "dG")
        if len(res) == 3:
            q, d, rho = res
            eigen[s.name][qk] = q
            eigen[s.name][dk] = d
            eigen[s.name]["rhoA" if s.factor == "A" else "rhoG"] = rho
            continue
        q, d = res
        eigen[s.name][qk] = (
            eigen[s.name][qk].at[s.start : s.stop, s.start : s.stop].set(q)
        )
        eigen[s.name][dk] = eigen[s.name][dk].at[s.start : s.stop].set(d)
    return eigen


def sharded_eigen_update(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    assignment: Assignment,
    mesh: Mesh,
    axis_name: str = "data",
    eps: float = 1e-10,
    granularity: int = 512,
    minimum: int = 128,
    rank_fn=None,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Recompute all layers' eigendecompositions, sharded over the WHOLE mesh.

    ``factors`` is the replicated ``{layer: {'A', 'G'}}`` dict; returns the
    replicated ``{layer: {'QA', 'dA', 'QG', 'dG'}}`` dict with work placed
    per ``assignment`` (see module docstring for the SPMD plan). Owners are
    FLAT device indices over every mesh axis (row-major in ``mesh.axis_names``
    order) — a data×seq mesh splits eigh work across all devices instead of
    replicating it per non-data axis (the reference's Horovod world has no
    axes to begin with; every rank is an eigh worker,
    kfac_preconditioner.py:383-396). ``axis_name`` is unused and kept for
    call-site compatibility.

    ``rank_fn`` (solver="rsvd") diverts slots it maps to a rank into the
    randomized truncated solve: their buckets run batched matmuls instead of
    QDWH eigh and their sum-of-zeros exchange psums the far smaller
    ``[k, m, r]``/``[k, r]`` tables — the broadcast-bytes win scales with
    n/r. The residual mass ``rho`` is computed from the replicated factor
    trace, so it needs no exchange at all.
    """
    del axis_name
    axes = tuple(mesh.axis_names)
    world = mesh.devices.size
    slots = build_slots(factors, assignment)
    groups, lr_groups = _rank_groups(slots, rank_fn, granularity, minimum)

    # Host-side per-bucket index tables: device -> the stack rows it owns.
    tables = {
        m: _owner_tables(slots, idxs, world) for m, idxs in groups.items()
    }
    lr_tables = {
        key: _owner_tables(slots, idxs, world)
        for key, idxs in lr_groups.items()
    }

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=P(),
        out_specs=P(),
        check_vma=False,
    )
    def _inner(facs):
        # phase() marks eigh vs exchange on both clocks (observability/
        # phases.py): a named scope on the ops for the device trace, and a
        # trace-time telemetry span (we are inside shard_map/jit); neither
        # costs anything in the compiled program
        # flat device index over ALL mesh axes, row-major in axis_names order
        dev = lax.axis_index(axes[0])
        for a in axes[1:]:
            dev = dev * mesh.shape[a] + lax.axis_index(a)
        per_slot: Dict[int, Tuple[jnp.ndarray, ...]] = {}
        for m, idxs in groups.items():
            with phase("kfac_refresh", "trace/eigh/compute"):
                all_blocks = _padded_stack(facs, slots, idxs, m)  # [k, m, m]
                idx_tab, valid = tables[m]
                mine = jnp.take(idx_tab, dev, axis=0)  # [rows]
                vmask = jnp.take(valid, dev, axis=0)  # [rows]
                stack = jnp.take(all_blocks, mine, axis=0)  # [rows, m, m]
                q, d = batched_eigh(stack)
                q = q * vmask[:, None, None]
                d = d * vmask[:, None]
            k = len(idxs)
            with phase("kfac_exchange", "trace/eigh/exchange"):
                # Sum-of-zeros exchange: scatter-add my rows, psum the rest in.
                kq = jnp.zeros((k, m, m), jnp.float32).at[mine].add(q)
                kd = jnp.zeros((k, m), jnp.float32).at[mine].add(d)
                kq = lax.psum(kq, axes)
                kd = lax.psum(kd, axes)
            for row, i in enumerate(idxs):
                per_slot[i] = unpad_eigh(kq[row], kd[row], slots[i].size, eps)
        for (m, rank), idxs in lr_groups.items():
            with phase("kfac_refresh", "trace/eigh/compute"):
                all_blocks = _rsvd_stack(facs, slots, idxs, m)  # [k, m, m]
                idx_tab, valid = lr_tables[(m, rank)]
                mine = jnp.take(idx_tab, dev, axis=0)
                vmask = jnp.take(valid, dev, axis=0)
                stack = jnp.take(all_blocks, mine, axis=0)
                q, d = batched_randomized_eigh(stack, rank, eps)
                q = q * vmask[:, None, None]
                d = d * vmask[:, None]
            k = len(idxs)
            with phase("kfac_exchange", "trace/eigh/exchange"):
                kq = jnp.zeros((k, m, rank), jnp.float32).at[mine].add(q)
                kd = jnp.zeros((k, rank), jnp.float32).at[mine].add(d)
                kq = lax.psum(kq, axes)
                kd = lax.psum(kd, axes)
            for row, i in enumerate(idxs):
                s = slots[i]
                blk = facs[s.name][s.factor][
                    s.start : s.stop, s.start : s.stop
                ].astype(jnp.float32)
                rho = residual_rho(jnp.trace(blk), kd[row], s.size, rank)
                per_slot[i] = (kq[row, : s.size, :], kd[row], rho)
        return _assemble(facs, slots, per_slot)

    return _inner(factors)


def _scatter_into(
    pending: Dict[str, Dict[str, jnp.ndarray]],
    slots: List[EighSlot],
    results: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]],
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Scatter per-slot (Q, d) into an EXISTING eigen buffer dict.

    The chunked-refresh analog of :func:`_assemble`: instead of starting from
    zeroed buffers (a full refresh writes every slot), each chunk overwrites
    only its own slots' block regions of the double-buffered
    ``eigen_pending`` state, leaving other chunks' landed results in place.
    Q casts to the buffer's storage dtype (``eigen_dtype``) at the write —
    elementwise, so the swapped basis is bit-identical to the monolithic
    path's whole-dict downcast.
    """
    out = {name: dict(e) for name, e in pending.items()}
    for i, s in enumerate(slots):
        res = results[i]
        qk, dk = ("QA", "dA") if s.factor == "A" else ("QG", "dG")
        buf = out[s.name][qk]
        if len(res) == 3:
            # truncated slot: whole-factor span guaranteed (rsvd excludes
            # diag_blocks > 1), so the chunk overwrites the entire entry
            q, d, rho = res
            out[s.name][qk] = q.astype(buf.dtype)
            out[s.name][dk] = d
            out[s.name]["rhoA" if s.factor == "A" else "rhoG"] = rho
            continue
        q, d = res
        out[s.name][qk] = (
            buf.at[s.start : s.stop, s.start : s.stop].set(q.astype(buf.dtype))
        )
        out[s.name][dk] = out[s.name][dk].at[s.start : s.stop].set(d)
    return out


def sharded_eigen_chunk_update(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    pending: Dict[str, Dict[str, jnp.ndarray]],
    chunk_slots: List[EighSlot],
    mesh: Mesh,
    eps: float = 1e-10,
    granularity: int = 512,
    minimum: int = 128,
    rank_fn=None,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """One chunk of the pipelined refresh, sharded over the WHOLE mesh.

    Same SPMD plan as :func:`sharded_eigen_update` — per-bucket index
    tables, one batched eigh per bucket, sum-of-zeros psum — restricted to
    ``chunk_slots`` and scattering results into the replicated ``pending``
    buffers instead of assembling from zeros. Owners are rebalanced WITHIN
    the chunk (``eigh_chunk_owners``, rank-aware when ``rank_fn`` is set) so
    each pipelined step spreads its fraction of the eigh work across all
    devices.
    """
    from kfac_pytorch_tpu.parallel.assignment import eigh_chunk_owners

    axes = tuple(mesh.axis_names)
    world = mesh.devices.size
    owners = eigh_chunk_owners(chunk_slots, world, granularity, minimum, rank_fn)
    slots = [dataclasses.replace(s, owner=o) for s, o in zip(chunk_slots, owners)]
    groups, lr_groups = _rank_groups(slots, rank_fn, granularity, minimum)

    tables = {
        m: _owner_tables(slots, idxs, world) for m, idxs in groups.items()
    }
    lr_tables = {
        key: _owner_tables(slots, idxs, world)
        for key, idxs in lr_groups.items()
    }

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=P(),
        out_specs=P(),
        check_vma=False,
    )
    def _inner(facs):
        dev = lax.axis_index(axes[0])
        for a in axes[1:]:
            dev = dev * mesh.shape[a] + lax.axis_index(a)
        per_slot: Dict[int, Tuple[jnp.ndarray, ...]] = {}
        for m, idxs in groups.items():
            with phase("kfac_refresh", "trace/eigh/compute"):
                all_blocks = _padded_stack(facs, slots, idxs, m)  # [k, m, m]
                idx_tab, valid = tables[m]
                mine = jnp.take(idx_tab, dev, axis=0)
                vmask = jnp.take(valid, dev, axis=0)
                stack = jnp.take(all_blocks, mine, axis=0)
                q, d = batched_eigh(stack)
                q = q * vmask[:, None, None]
                d = d * vmask[:, None]
            k = len(idxs)
            with phase("kfac_exchange", "trace/eigh/exchange"):
                kq = jnp.zeros((k, m, m), jnp.float32).at[mine].add(q)
                kd = jnp.zeros((k, m), jnp.float32).at[mine].add(d)
                kq = lax.psum(kq, axes)
                kd = lax.psum(kd, axes)
            for row, i in enumerate(idxs):
                per_slot[i] = unpad_eigh(kq[row], kd[row], slots[i].size, eps)
        for (m, rank), idxs in lr_groups.items():
            with phase("kfac_refresh", "trace/eigh/compute"):
                all_blocks = _rsvd_stack(facs, slots, idxs, m)
                idx_tab, valid = lr_tables[(m, rank)]
                mine = jnp.take(idx_tab, dev, axis=0)
                vmask = jnp.take(valid, dev, axis=0)
                stack = jnp.take(all_blocks, mine, axis=0)
                q, d = batched_randomized_eigh(stack, rank, eps)
                q = q * vmask[:, None, None]
                d = d * vmask[:, None]
            k = len(idxs)
            with phase("kfac_exchange", "trace/eigh/exchange"):
                kq = jnp.zeros((k, m, rank), jnp.float32).at[mine].add(q)
                kd = jnp.zeros((k, rank), jnp.float32).at[mine].add(d)
                kq = lax.psum(kq, axes)
                kd = lax.psum(kd, axes)
            for row, i in enumerate(idxs):
                s = slots[i]
                blk = facs[s.name][s.factor][
                    s.start : s.stop, s.start : s.stop
                ].astype(jnp.float32)
                rho = residual_rho(jnp.trace(blk), kd[row], s.size, rank)
                per_slot[i] = (kq[row, : s.size, :], kd[row], rho)
        return per_slot

    # the post-psum results are replicated, so the pending-buffer scatter can
    # live outside the shard_map (identical program, simpler out pytree)
    return _scatter_into(pending, slots, _inner(factors))


def replicated_eigen_chunk_update(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    pending: Dict[str, Dict[str, jnp.ndarray]],
    chunk_slots: List[EighSlot],
    eps: float = 1e-10,
    granularity: int = 512,
    minimum: int = 128,
    rank_fn=None,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Single-device chunk path: the chunk's jobs, bucketed, scattered into
    ``pending`` (the world=1 twin of :func:`sharded_eigen_chunk_update`)."""
    results = _replicated_results(
        factors, chunk_slots, eps, granularity, minimum, rank_fn
    )
    return _scatter_into(pending, chunk_slots, results)


def _replicated_results(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    slots: List[EighSlot],
    eps: float,
    granularity: int,
    minimum: int,
    rank_fn,
) -> Dict[int, Tuple[jnp.ndarray, ...]]:
    """Local (world=1) per-slot solves: dense slots through ``bucketed_eigh``,
    rank-mapped slots through ``bucketed_rsvd_eigh`` — the single-device twin
    of the sharded dense/LR bucket split."""
    from kfac_pytorch_tpu.ops.eigh import bucketed_eigh
    from kfac_pytorch_tpu.ops.rsvd import bucketed_rsvd_eigh

    def _block(s: EighSlot) -> jnp.ndarray:
        return factors[s.name][s.factor][
            s.start : s.stop, s.start : s.stop
        ].astype(jnp.float32)

    dense_idx, by_rank = _split_by_rank(slots, rank_fn)
    results: Dict[int, Tuple[jnp.ndarray, ...]] = {}
    dense = bucketed_eigh(
        [_block(slots[i]) for i in dense_idx], eps, granularity, minimum
    )
    for j, i in enumerate(dense_idx):
        results[i] = dense[j]
    for rank, idxs in sorted(by_rank.items()):
        lr = bucketed_rsvd_eigh(
            [_block(slots[i]) for i in idxs], rank, eps, granularity, minimum
        )
        for j, i in enumerate(idxs):
            results[i] = lr[j]
    return results


# ---------------------------------------------------------------------------
# Owner-sharded refresh (factor_sharding="owner")
# ---------------------------------------------------------------------------
#
# In owner-sharded mode there is nothing to exchange: each device's local
# shard of the ``{"n<size>": [world·rows, n, n]}`` factor stacks already IS
# exactly the slot set it owns, so the refresh is one shard_map whose per-
# device program decomposes its local rows and writes its local eigen-shard
# rows — zero collectives, O(model/devices) compute and memory. The padded
# shape-bucket discipline is unchanged (same pad/unpad helpers as the
# replicated paths, so per-matrix results match the replicated refresh);
# pad rows of under-loaded devices decompose decayed garbage that no solve
# ever reads.


def _owner_group_solve(
    local: jnp.ndarray,
    n: int,
    rank: Optional[int],
    eps: float,
    granularity: int,
    minimum: int,
    eigen_dtype,
) -> Dict[str, jnp.ndarray]:
    """Decompose one size-group's local ``[rows, n, n]`` shard stack.

    Returns the group's eigen-shard entry: dense ``{"Q" [rows, n, n], "d"
    [rows, n]}`` or truncated ``{"Q" [rows, n, r], "d" [rows, r], "rho"
    [rows]}``, with Q stored at ``eigen_dtype`` exactly like the replicated
    paths' whole-dict downcast.
    """
    m = bucket_size(n, granularity, minimum)
    sym = symmetrize(local.astype(jnp.float32))
    if rank is None:
        stack = jax.vmap(lambda b: pad_for_eigh(b, m))(sym)
        q, d = batched_eigh(stack)
        q, d = jax.vmap(lambda qq, dd: unpad_eigh(qq, dd, n, eps))(q, d)
        return {"Q": q.astype(eigen_dtype), "d": d}
    stack = jax.vmap(lambda b: pad_for_rsvd(b, m))(sym)
    q, d = batched_randomized_eigh(stack, rank, eps)
    traces = jnp.trace(sym, axis1=-2, axis2=-1)
    rho = jax.vmap(lambda t, dd: residual_rho(t, dd, n, rank))(traces, d)
    return {"Q": q[:, :n, :].astype(eigen_dtype), "d": d, "rho": rho}


def owner_eigen_update(
    factor_shard: Dict[str, jnp.ndarray],
    plan,
    mesh: Mesh,
    axis_name: str = "data",
    eps: float = 1e-10,
    granularity: int = 512,
    minimum: int = 128,
    rank_fn=None,
    eigen_dtype=jnp.float32,
) -> Dict[str, jnp.ndarray]:
    """Monolithic owner-local refresh of every factor shard row.

    ``factor_shard`` is the sharded ``{"n<size>": [world·rows, n, n]}``
    stack dict from the owner-mode KFAC state; returns the matching
    ``{"n<size>": {"Q", "d"[, "rho"]}}`` eigen-shard dict, sharded the same
    way. Purely owner-local — no collective appears in the program.
    """

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(axis_name), factor_shard),),
        out_specs=_owner_eigen_specs(plan, rank_fn, axis_name),
        check_vma=False,
    )
    def _inner(shard):
        out = {}
        for n in plan.group_sizes:
            rank = rank_fn(n) if rank_fn is not None else None
            with phase("kfac_refresh", "trace/eigh/compute"):
                out[f"n{n}"] = _owner_group_solve(
                    shard[f"n{n}"], n, rank, eps, granularity, minimum,
                    eigen_dtype,
                )
        return out

    return _inner(factor_shard)


def _owner_eigen_specs(plan, rank_fn, axis_name: str):
    """Out-spec pytree matching the owner eigen-shard structure."""
    specs = {}
    for n in plan.group_sizes:
        rank = rank_fn(n) if rank_fn is not None else None
        entry = {"Q": P(axis_name), "d": P(axis_name)}
        if rank is not None:
            entry["rho"] = P(axis_name)
        specs[f"n{n}"] = entry
    return specs


def owner_eigen_chunk_update(
    factor_shard: Dict[str, jnp.ndarray],
    pending_shard: Dict[str, Dict[str, jnp.ndarray]],
    jobs: List[Tuple[int, int]],
    plan,
    mesh: Mesh,
    axis_name: str = "data",
    eps: float = 1e-10,
    granularity: int = 512,
    minimum: int = 128,
    rank_fn=None,
    eigen_dtype=jnp.float32,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """One chunk of the pipelined owner-local refresh.

    ``jobs`` is this chunk's static ``(size, row)`` list from
    ``parallel.assignment.plan_owner_chunks`` — every device decomposes the
    SAME local rows of the same groups (SPMD-uniform program) and overwrites
    just those rows of its ``eigen_pending_shard``, the owner-mode analog of
    :func:`_scatter_into`. Empty chunks return ``pending_shard`` unchanged.
    """
    if not jobs:
        return pending_shard
    by_group: Dict[int, List[int]] = {}
    for n, r in jobs:
        by_group.setdefault(n, []).append(r)

    shard_specs = jax.tree_util.tree_map(lambda _: P(axis_name), factor_shard)
    pending_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name), pending_shard
    )

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(shard_specs, pending_specs),
        out_specs=pending_specs,
        check_vma=False,
    )
    def _inner(shard, pending):
        out = {k: dict(v) for k, v in pending.items()}
        for n in sorted(by_group):
            rows = jnp.asarray(sorted(by_group[n]), jnp.int32)
            rank = rank_fn(n) if rank_fn is not None else None
            with phase("kfac_refresh", "trace/eigh/compute"):
                sub = jnp.take(shard[f"n{n}"], rows, axis=0)
                res = _owner_group_solve(
                    sub, n, rank, eps, granularity, minimum, eigen_dtype
                )
            key = f"n{n}"
            for field, val in res.items():
                out[key][field] = out[key][field].at[rows].set(
                    val.astype(out[key][field].dtype)
                )
        return out

    return _inner(factor_shard, pending_shard)


def owner_spectrum_mass(
    factor_shard: Dict[str, jnp.ndarray],
    eigen_shard: Dict[str, Dict[str, jnp.ndarray]],
    plan,
    mesh: Mesh,
    axis_name: str = "data",
    rank_fn=None,
) -> jnp.ndarray:
    """Captured-spectrum fraction over all truncated slots (owner mode).

    The owner-sharded twin of the preconditioner's ``_spectrum_mass``: each
    device sums its VALID rows' kept eigenvalue mass and factor traces (pad
    rows masked by the plan's validity table), one psum pair merges the
    partials, and the replicated scalar matches the replicated metric up to
    summation order.
    """
    import numpy as np

    valid = {
        n: jnp.asarray(np.asarray(plan.valid_rows(n)), jnp.float32)
        for n in plan.group_sizes
        if rank_fn is not None and rank_fn(n) is not None
    }
    if not valid:
        return jnp.float32(1.0)
    axes = tuple(mesh.axis_names)

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: P(axis_name), factor_shard),
            jax.tree_util.tree_map(lambda _: P(axis_name), eigen_shard),
        ),
        out_specs=P(),
        check_vma=False,
    )
    def _inner(shard, eigen):
        # the shard stacks (and the plan's validity table) are laid out over
        # the FACTOR axis only — on a 2-D data×tensor mesh every tensor
        # replica holds the same rows, so the row index is the data-axis
        # coordinate, not the flat mesh index
        dev = lax.axis_index(axis_name)
        cap = jnp.float32(0.0)
        tot = jnp.float32(0.0)
        for n, vtab in valid.items():
            vmask = jnp.take(vtab, dev, axis=0)  # [rows]
            d = eigen[f"n{n}"]["d"]  # [rows, r]
            traces = jnp.trace(
                shard[f"n{n}"].astype(jnp.float32), axis1=-2, axis2=-1
            )
            cap = cap + jnp.sum(d * vmask[:, None])
            tot = tot + jnp.sum(traces * vmask)
        cap = lax.psum(cap, axes)
        tot = lax.psum(tot, axes)
        return cap / jnp.maximum(tot, 1e-30)

    return _inner(factor_shard, eigen_shard)


def owner_stream_fold(
    factor_shard: Dict[str, jnp.ndarray],
    eigen_shard: Dict[str, Dict[str, jnp.ndarray]],
    plan,
    mesh: Mesh,
    axis_name: str = "data",
    eps: float = 1e-10,
    rank_fn=None,
) -> Tuple[Dict[str, Dict[str, jnp.ndarray]], jnp.ndarray]:
    """Owner-sharded streaming fold (ops/streaming.py, owner form).

    Each device folds its own shard rows' freshly merged factors through the
    on-owner bases — ``d = diag(Qᵀ F Q)`` per row via two batched einsums,
    ``rho`` from the leftover trace — and contributes its valid rows to the
    drift gauge; one psum pair merges the residual partials into a
    replicated scalar. ``Q`` stacks pass through untouched, so the compiled
    capture step stays matmul-only (zero eigh custom-calls) and the only
    collective is the gauge psum. Pad rows hold zero factors (fed only by
    the EMA decay), fold to zeros harmlessly, and are masked out of the
    gauge by the plan's validity table. Returns
    ``(new_eigen_shard, residual)``.
    """
    import numpy as np

    valid = {
        n: jnp.asarray(np.asarray(plan.valid_rows(n)), jnp.float32)
        for n in plan.group_sizes
        if rank_fn is not None and rank_fn(n) is not None
    }
    axes = tuple(mesh.axis_names)
    eigen_specs = jax.tree_util.tree_map(lambda _: P(axis_name), eigen_shard)

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: P(axis_name), factor_shard),
            eigen_specs,
        ),
        out_specs=(eigen_specs, P()),
        check_vma=False,
    )
    def _inner(shard, eigen):
        dev = lax.axis_index(axis_name)
        num = jnp.float32(0.0)
        den = jnp.float32(0.0)
        out = {}
        for n in plan.group_sizes:
            key = f"n{n}"
            rank = rank_fn(n) if rank_fn is not None else None
            q = eigen[key]["Q"].astype(jnp.float32)  # [rows, n, r|n]
            f = symmetrize(shard[key].astype(jnp.float32))
            t = jnp.einsum(
                "bij,bjr->bir", f, q, precision=lax.Precision.HIGHEST
            )
            d = jnp.einsum(
                "bir,bir->br", t, q, precision=lax.Precision.HIGHEST
            )
            d = d * (d > eps)
            entry = {"Q": eigen[key]["Q"], "d": d}
            if rank is not None:
                traces = jnp.trace(f, axis1=-2, axis2=-1)
                leftover = jnp.maximum(traces - jnp.sum(d, axis=-1), 0.0)
                entry["rho"] = leftover / float(max(n - rank, 1))
                vmask = jnp.take(valid[n], dev, axis=0)  # [rows]
                num = num + jnp.sum(leftover * vmask)
                den = den + jnp.sum(traces * vmask)
            out[key] = entry
        for n in plan.diag_group_sizes:
            key = f"v{n}"
            diag = shard[key].astype(jnp.float32)
            out[key] = {"d": diag * (diag > eps)}
        num = lax.psum(num, axes)
        den = lax.psum(den, axes)
        return out, num / jnp.maximum(den, 1e-30)

    return _inner(factor_shard, eigen_shard)


def replicated_eigen_update(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    diag_blocks_per_layer: Dict[str, int],
    eps: float = 1e-10,
    granularity: int = 512,
    minimum: int = 128,
    rank_fn=None,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Single-device path: every job computed locally, still shape-bucketed.

    Identical math to :func:`sharded_eigen_update` with world=1 — the bucketed
    batched eigh is what keeps single-chip ResNet-50 compile times sane.
    """
    slots = build_slots(factors, None, diag_blocks_per_layer)
    results = _replicated_results(
        factors, slots, eps, granularity, minimum, rank_fn
    )
    return _assemble(factors, slots, results)
