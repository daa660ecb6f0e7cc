"""Versioned, sharding-aware snapshot I/O for the full K-FAC training state.

The durability layer of the elastic runtime (docs/ELASTIC.md): every state
key any lever can create — factor EMAs, eigen bases and their
``eigen_pending`` double buffers, the rsvd Q/d/rho tables inside the eigen
entries, the ``factor_sync_age``/``eigen_swap_slip`` counters — is named in
:data:`KFAC_STATE_KEYS`, and a snapshot is refused if the live state carries
a key outside that manifest (``scripts/check_state_manifest.py`` holds the
static side of the same contract, so a future lever cannot silently drift
out of checkpoints).

A snapshot is an orbax pytree directory plus ``kfac_manifest.json`` written
AFTER the payload commits — a kill mid-write leaves no manifest, and the
scan-resume path (:func:`latest_snapshot`) skips such incomplete or corrupt
directories instead of crashing on them. The manifest carries what the
device pytree cannot: the resolved planner :class:`Plan` (its existing
``to_state`` int encoding), the owner-shard plan fingerprint, the host-side
:class:`EigenRefreshCadence` interval state (without which a mid-interval
resume would re-bootstrap and diverge), and the data world the shard stacks
were sized to (what the resize replan re-plans from).

Multi-host correctness: the old ``training/checkpoint.py`` path ran
``jax.device_get`` on process 0 only, which silently cannot see other
hosts' owner shards. :func:`save_pytree` keeps that single-host path
bitwise-identical but, with ``jax.process_count() > 1``, hands orbax the
live global arrays from EVERY process so each shard is written by a host
that can address it.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

MANIFEST_VERSION = 1
MANIFEST_NAME = "kfac_manifest.json"
STATE_SUBDIR = "state"
_SNAP_PREFIX = "snap-"

#: Every top-level key the K-FAC state pytree can carry, by lever.
#: ``scripts/check_state_manifest.py`` statically greps ``state[...]``
#: writes in the package against this table — add the key HERE when a new
#: lever adds state, or the lint (and snapshots of that state) fail.
KFAC_STATE_KEYS: Dict[str, str] = {
    "step": "global update counter (int32 scalar)",
    "factors": "per-layer A/A_diag/G running averages "
               "(owner mode: scalar placeholders keeping the name registry)",
    "eigen": "per-layer eigen entries for singleton shapes "
             "(QA/dA[/rhoA], QG/dG[/rhoG] or iA/iG; rsvd tables included)",
    "eigen_stacked": "batched eigen entries for same-shape layer groups "
                     "(<g>x<a> stacks)",
    "inverse_tables": "the inverse method's iA/iG of one side in one table "
                      "{side: [K, side, side]} (expert banks or shared_a; "
                      "eigen / eigen_stacked are empty then)",
    "eigen_pending": "chunked-refresh double buffer in full per-layer form "
                     "(eigh_chunks > 1, replicated mode)",
    "factor_shard": "owner-sharded factor stacks n<size>/v<size>, leading "
                    "axis world*rows split over the mesh",
    "eigen_shard": "owner-sharded eigen stacks (Q/d[/rho] per size group)",
    "eigen_pending_shard": "owner-sharded pending double buffer "
                           "(eigh_chunks > 1, owner mode)",
    "factor_local": "per-replica local factor accumulators between deferred "
                    "flushes (owner mode, factor_comm_freq > 1)",
    "wire_error": "per-replica int8-wire error-feedback residuals, one flat "
                  "f32 buffer per comm bucket (factor_comm_dtype='int8')",
    "factor_sync_age": "capture steps since the last cross-replica factor "
                       "merge (int32 scalar, 0 = globally synced)",
    "spectrum_mass": "trace fraction the truncated bases captured at the "
                     "last refresh (solver='rsvd'/'streaming')",
    "stream_residual": "drift gauge: curvature mass fraction outside the "
                       "retained bases after the last fold "
                       "(solver='streaming', f32 scalar)",
    "stream_fold_steps": "capture folds since the last re-orthonormalization "
                         "(solver='streaming', int32 scalar)",
    "eigen_swap_slip": "1 while a fully-landed pending basis awaits its "
                       "slipped swap (staleness_budget > 0)",
    "diagnostics": "in-graph health diagnostics (track_diagnostics=True)",
}


#: State keys holding per-REPLICA data inside replicated-spec arrays —
#: device copies genuinely differ, so snapshots must pack every device's
#: shard (see :func:`pack_replica_local`). ``factor_local``: deferred
#: factor accumulators; ``wire_error``: int8-wire error-feedback residuals
#: (each replica carries its own quantization residue between flushes).
_REPLICA_LOCAL_KEYS: Tuple[str, ...] = ("factor_local", "wire_error")


class SnapshotError(RuntimeError):
    """A snapshot is unreadable, incomplete, or from a different contract."""


def manifest_keys() -> frozenset:
    return frozenset(KFAC_STATE_KEYS)


def kfac_state_of(state: Any) -> Optional[Dict[str, Any]]:
    """The K-FAC state dict inside ``state`` (a TrainState or the dict
    itself), or None when the tree carries no curvature state."""
    inner = getattr(state, "kfac_state", None)
    if inner is not None:
        return inner
    if isinstance(state, dict) and "factors" in state:
        return state
    return None


def validate_state_keys(kfac_state: Optional[Dict[str, Any]]) -> List[str]:
    """The sorted key list, refusing keys outside the manifest."""
    if kfac_state is None:
        return []
    unknown = sorted(set(kfac_state) - manifest_keys())
    if unknown:
        raise SnapshotError(
            f"K-FAC state carries keys outside the state_io manifest: "
            f"{unknown} — add them to KFAC_STATE_KEYS (and the docs) before "
            f"they can be snapshot"
        )
    return sorted(kfac_state)


def save_pytree(path: str, tree: Any) -> None:
    """Sharding-aware orbax write of an arbitrary pytree.

    Single process: identical to the historical path (host ``device_get``
    then write — bitwise-stable on-disk form). Multi-process: every process
    passes the live global arrays so orbax writes owner shards from hosts
    that address them instead of silently dropping them.
    """
    ckptr = ocp.PyTreeCheckpointer()
    if jax.process_count() > 1:
        ckptr.save(path, tree, force=True)
    elif jax.process_index() == 0:
        ckptr.save(path, jax.device_get(tree), force=True)


def restore_pytree(path: str, target: Any = None) -> Any:
    ckptr = ocp.PyTreeCheckpointer()
    if target is None:
        return ckptr.restore(path)
    return ckptr.restore(path, item=target)


def _plan_encoding(kfac: Any) -> Optional[Dict[str, int]]:
    """The resolved planner Plan's ``to_state`` encoding, as plain ints."""
    plan = getattr(kfac, "plan", None)
    if plan is None:
        return None
    return {k: int(v) for k, v in plan.to_state().items()}


def _shard_fingerprint(kfac: Any) -> Optional[str]:
    """Digest of the owner-shard layout the live state was placed by —
    available once init()/update() derived the (single) cached plan."""
    plans = getattr(kfac, "_shard_plans", None)
    if not plans or len(plans) != 1:
        return None
    from kfac_pytorch_tpu.parallel.assignment import plan_fingerprint

    return plan_fingerprint(next(iter(plans.values())))


def build_manifest(
    state: Any,
    kfac: Any = None,
    cadence: Any = None,
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """The JSON manifest describing ``state`` — everything restore/replan
    needs that the device pytree itself cannot carry."""
    kstate = kfac_state_of(state)
    keys = validate_state_keys(kstate)
    sharding = "none"
    if kstate is not None:
        sharding = "owner" if "factor_shard" in kstate else "replicated"
    step = getattr(state, "step", None)
    if step is None and isinstance(state, dict):
        step = state.get("step")
    manifest: Dict[str, Any] = {
        "format": "kfac-elastic-snapshot",
        "version": MANIFEST_VERSION,
        "step": int(jax.device_get(step)) if step is not None else None,
        "kfac_state_keys": keys,
        "sharding": sharding,
        "world": (
            int(kfac._data_world()) if kfac is not None
            else int(jax.device_count())
        ),
        "plan": _plan_encoding(kfac) if kfac is not None else None,
        "shard_plan_fingerprint": (
            _shard_fingerprint(kfac) if kfac is not None else None
        ),
        "cadence": cadence.state_dict() if cadence is not None else None,
        "extra": dict(extra or {}),
    }
    return manifest


def _with_kfac_state(state: Any, kstate: Dict[str, Any]) -> Any:
    if hasattr(state, "replace"):
        return state.replace(kfac_state=kstate)
    return kstate


def pack_replica_local(state: Any, mesh: Any = None) -> Tuple[Any, bool]:
    """Stack every :data:`_REPLICA_LOCAL_KEYS` entry's per-replica shards
    into a ``(world, ...)`` leading axis; returns ``(state, packed)``.

    ``factor_local`` (and the int8 wire's ``wire_error`` residuals, which
    ride the same way) is per-REPLICA data in a replicated-spec array:
    each device accumulates its own batch shard's statistics between
    deferred flushes, so the device copies genuinely differ and a plain
    ``jax.device_get`` silently keeps only device 0's accumulator —
    broadcasting that on restore would make every replica flush device 0's
    partial sums and break bitwise mid-flush-window resume. Packing reads
    every device's shard (in mesh order when ``mesh`` is given) while the
    live arrays are still addressable; :func:`unpack_replica_local` puts
    each row back on its device at restore.

    Multi-process runs cannot host-stack (cross-host shards are not
    addressable here), so the pack instead builds a GLOBAL ``(world, ...)``
    array sharded one-row-per-device over a flat mesh of the same devices:
    each process contributes only the rows it can address
    (``make_array_from_single_device_arrays``), and the multi-process
    :func:`save_pytree` branch hands orbax that live global array so every
    host writes its own replicas' accumulators — deferred accumulation is
    lossless off flush boundaries across hosts too.
    """
    kstate = kfac_state_of(state)
    if kstate is None:
        return state, False
    keys = [k for k in _REPLICA_LOCAL_KEYS if k in kstate]
    if not keys:
        return state, False
    leaves = jax.tree_util.tree_leaves({k: kstate[k] for k in keys})
    if not leaves or not hasattr(leaves[0], "addressable_shards"):
        return state, False  # already host-side: per-replica info is gone
    devs = (
        list(mesh.devices.flat) if mesh is not None
        else sorted(jax.devices(), key=lambda d: d.id)
    )
    order = {d.id: i for i, d in enumerate(devs)}

    if jax.process_count() > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        flat = Mesh(np.asarray(devs), ("packed",))
        row_sharding = NamedSharding(flat, PartitionSpec("packed"))

        def pack(x):
            shards = sorted(
                x.addressable_shards, key=lambda s: order[s.device.id]
            )
            rows = [s.data.reshape((1,) + tuple(s.data.shape))
                    for s in shards]
            return jax.make_array_from_single_device_arrays(
                (len(devs),) + tuple(x.shape), row_sharding, rows
            )
    else:
        def pack(x):
            shards = sorted(
                x.addressable_shards, key=lambda s: order[s.device.id]
            )
            return np.stack([np.asarray(s.data) for s in shards])

    packed = {k: jax.tree_util.tree_map(pack, kstate[k]) for k in keys}
    return _with_kfac_state(state, {**kstate, **packed}), True


def stack_local_template(target: Any, world: int) -> Any:
    """Give ``target``'s replica-local leaves (:data:`_REPLICA_LOCAL_KEYS`)
    the packed ``(world, ...)`` shape so orbax restores a packed snapshot
    into a matching template."""
    kstate = kfac_state_of(target)
    if kstate is None:
        return target
    keys = [k for k in _REPLICA_LOCAL_KEYS if k in kstate]
    if not keys:
        return target
    stacked = {
        k: jax.tree_util.tree_map(
            lambda x: np.zeros((int(world),) + tuple(np.shape(x)), x.dtype),
            kstate[k],
        )
        for k in keys
    }
    return _with_kfac_state(target, {**kstate, **stacked})


def unpack_replica_local(state: Any, mesh: Any) -> Any:
    """Inverse of :func:`pack_replica_local` on the same-size mesh: row i of
    each packed leaf becomes mesh device i's replica-local copy again (a
    replicated-spec array with deliberately divergent shards — exactly the
    form the live deferred accumulation produces). Multi-process: each
    process puts only the rows of its own addressable devices (the restored
    packed array is host-replicated, so every host sees all rows)."""
    kstate = kfac_state_of(state)
    if kstate is None:
        return state
    keys = [k for k in _REPLICA_LOCAL_KEYS if k in kstate]
    if not keys:
        return state
    from jax.sharding import NamedSharding, PartitionSpec

    devs = list(mesh.devices.flat)
    spec = NamedSharding(mesh, PartitionSpec())
    mine = jax.process_index()

    def unpack(x):
        x = np.asarray(jax.device_get(x))
        if x.shape[0] != len(devs):
            raise SnapshotError(
                f"packed replica-local world {x.shape[0]} != mesh size "
                f"{len(devs)} — resize replans drop deferred accumulators"
            )
        bufs = [jax.device_put(x[i], d) for i, d in enumerate(devs)
                if d.process_index == mine]
        return jax.make_array_from_single_device_arrays(
            x.shape[1:], spec, bufs
        )

    unpacked = {k: jax.tree_util.tree_map(unpack, kstate[k]) for k in keys}
    return _with_kfac_state(state, {**kstate, **unpacked})


def snapshot_dir(directory: str, step: int) -> str:
    return os.path.join(os.path.abspath(directory), f"{_SNAP_PREFIX}{step}")


def save_snapshot(
    directory: str,
    step: int,
    state: Any,
    kfac: Any = None,
    cadence: Any = None,
    extra: Optional[Dict[str, Any]] = None,
    packed_replica_local: Optional[bool] = None,
) -> str:
    """Write one complete snapshot ``<directory>/snap-<step>``.

    The payload commits first; the manifest (with ``"complete": true``) is
    written last by process 0, so a mid-write kill is detectable — the
    scan-resume path treats a manifest-less directory as garbage.

    ``packed_replica_local=None`` packs live per-replica ``factor_local``
    shards here (see :func:`pack_replica_local`); a bool means the caller
    already packed (or deliberately skipped) and just records the fact.
    """
    if packed_replica_local is None:
        state, packed_replica_local = pack_replica_local(
            state, getattr(kfac, "mesh", None)
        )
    manifest = build_manifest(state, kfac=kfac, cadence=cadence, extra=extra)
    manifest["packed_replica_local"] = bool(packed_replica_local)
    if packed_replica_local:
        kst = kfac_state_of(state) or {}
        rows = jax.tree_util.tree_leaves(
            {k: kst[k] for k in _REPLICA_LOCAL_KEYS if k in kst}
        )
        if rows:
            # rows = mesh size (every device's replica accumulator), which
            # a 3-D mesh makes distinct from "world" (= data×fsdp replicas)
            manifest["packed_world"] = int(rows[0].shape[0])
    if manifest["step"] is None:
        manifest["step"] = int(step)
    snap = snapshot_dir(directory, step)
    save_pytree(os.path.join(snap, STATE_SUBDIR), state)
    if jax.process_index() == 0:
        manifest["complete"] = True
        tmp = os.path.join(snap, MANIFEST_NAME + ".tmp")
        with open(tmp, "w") as fh:
            json.dump(manifest, fh, indent=1)
        os.replace(tmp, os.path.join(snap, MANIFEST_NAME))
    return snap


def load_manifest(snap: str) -> Dict[str, Any]:
    """The manifest of one snapshot directory, validated."""
    path = os.path.join(snap, MANIFEST_NAME)
    if not os.path.isfile(path):
        raise SnapshotError(f"incomplete snapshot (no manifest): {snap}")
    try:
        with open(path) as fh:
            manifest = json.load(fh)
    except (OSError, ValueError) as e:
        raise SnapshotError(f"unreadable manifest in {snap}: {e}") from e
    if manifest.get("format") != "kfac-elastic-snapshot":
        raise SnapshotError(f"not a kfac elastic snapshot: {snap}")
    if manifest.get("version") != MANIFEST_VERSION:
        raise SnapshotError(
            f"snapshot version {manifest.get('version')} != "
            f"{MANIFEST_VERSION}: {snap}"
        )
    if not manifest.get("complete"):
        raise SnapshotError(f"snapshot marked incomplete: {snap}")
    return manifest


def list_snapshots(directory: str) -> List[Tuple[int, str]]:
    """``[(step, path)]`` of COMPLETE snapshots, newest last; incomplete or
    corrupt directories are skipped (scan-resume semantics)."""
    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return []
    out = []
    for name in sorted(os.listdir(directory)):
        if not name.startswith(_SNAP_PREFIX):
            continue
        tail = name[len(_SNAP_PREFIX):]
        if not tail.isdigit():
            continue
        snap = os.path.join(directory, name)
        try:
            load_manifest(snap)
        except SnapshotError:
            continue
        out.append((int(tail), snap))
    return sorted(out)


def latest_snapshot(directory: str) -> Optional[Tuple[int, str]]:
    snaps = list_snapshots(directory)
    return snaps[-1] if snaps else None


def restore_snapshot(
    snap: str,
    target: Any,
    kfac: Any = None,
    cadence: Any = None,
) -> Tuple[Any, Dict[str, Any]]:
    """``(state, manifest)`` from one snapshot directory.

    ``target`` gives the pytree structure (the freshly-initialized state).
    With ``kfac`` the restored K-FAC state is re-placed for its sharding
    mode (``rehome_kfac_state``: same-mesh owner resumes are bitwise); with
    ``cadence`` the host-side interval state recorded at save time is
    loaded back, making mid-interval resumes exact.
    """
    manifest = load_manifest(snap)
    packed = bool(manifest.get("packed_replica_local"))
    if packed and (manifest.get("packed_world") or manifest.get("world")):
        target = stack_local_template(
            target, int(manifest.get("packed_world") or manifest["world"])
        )
    state = restore_pytree(os.path.join(snap, STATE_SUBDIR), target)
    kstate = kfac_state_of(state)
    validate_state_keys(kstate)
    if kfac is not None and kstate is not None:
        from kfac_pytorch_tpu.training import checkpoint as _ckpt

        rehomed = _ckpt.rehome_kfac_state(kfac, kstate)
        if hasattr(state, "replace"):
            state = state.replace(kfac_state=rehomed)
        else:
            state = rehomed
        if (
            packed
            and getattr(kfac, "mesh", None) is not None
            and int(manifest.get("world") or 0) == int(kfac._data_world())
        ):
            state = unpack_replica_local(state, kfac.mesh)
    if cadence is not None and manifest.get("cadence") is not None:
        cadence.load_state_dict(manifest["cadence"])
    return state, manifest
