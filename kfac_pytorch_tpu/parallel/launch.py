"""Multi-host process bootstrap + topology: the MPI/Horovod-world equivalent.

The reference's distributed runtime is an externally-launched MPI world:
``mpiexec -hostfile ... -N 4 python examples/...`` (README.md:58-66) with
``hvd.init()`` + ``hvd.rank()/size()/local_rank()`` process topology
(kfac_preconditioner.py:128,134,211) and Horovod broadcast/barrier primitives
(pytorch_cifar10_resnet.py:129-135,197-198).

TPU-native equivalent: one process per host, connected by
``jax.distributed.initialize()`` (coordinator discovery is automatic on Cloud
TPU metadata; explicit via env/args elsewhere), with the global device mesh
spanning every chip of every host. Rank/size map to
``jax.process_index()/process_count()``; parameter broadcast is replaced by
functionally-replicated init under pjit (same seed everywhere ⇒ identical
params, no collective needed); host barriers and host-value agreement use a
tiny psum over the mesh.

Launch scripts live in ``scripts/tpu/`` (the sbatch/longhorn analog).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

_initialized = False


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Connect this process to the multi-host JAX runtime (``hvd.init`` analog).

    No-op for single-process runs (the common single-host case) and when
    called twice; raises what ``jax.distributed.initialize`` raises when a
    multi-process start fails. On Cloud TPU pods all arguments are discovered from the
    metadata server; on other clusters pass them or set
    ``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/``PROCESS_ID`` in the
    environment.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])
    # Decide from env only — querying jax.devices()/default_backend() here
    # would instantiate the backend before distributed init, which is too late.
    # A multi-process start that fails raises: a run that asked for several
    # processes must not carry on as one.
    if coordinator_address or num_processes:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif len(os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")) > 1:
        # Cloud TPU pod slice (multiple workers): auto-discovered.
        jax.distributed.initialize()
    _initialized = True


def rank() -> int:
    """Global process index (``hvd.rank()`` analog)."""
    return jax.process_index()


def size() -> int:
    """Global process count (``hvd.size()`` analog).

    NOTE: the reference's ``size()`` counts GPUs (1 proc/GPU); here a process
    drives all local chips, so device-level fan-out is ``device_count()``.
    """
    return jax.process_count()


def device_count() -> int:
    """Global chip count — the unit eigendecomposition work is sharded over."""
    return jax.device_count()


_local_rank_cache: Optional[int] = None


def local_rank() -> int:
    """Index of this process among processes on the same node
    (``hvd.local_rank()`` analog; used for e.g. per-node dataset staging).

    Resolution order: launcher-set env vars (torchrun / OpenMPI / MVAPICH2 /
    SLURM conventions), then — since nothing sets those on a plain TPU VM
    pod — a one-time allgather of hostnames, ranking this process among the
    processes that share its host by global process index. The collective
    result is cached (topology is static for the life of the world).

    WARNING: on a multi-process world without those env vars, the FIRST call
    is a blocking collective — every process must reach it. Do not call this
    only on some ranks (e.g. inside an ``is_primary()`` branch) or from
    mixed-environment launches where only some hosts set LOCAL_RANK; either
    pattern deadlocks the allgather.
    """
    global _local_rank_cache
    for var in (
        "LOCAL_RANK",
        "OMPI_COMM_WORLD_LOCAL_RANK",
        "MV2_COMM_WORLD_LOCAL_RANK",
        "SLURM_LOCALID",
    ):
        if var in os.environ:
            return int(os.environ[var])
    if jax.process_count() == 1:
        return 0
    if _local_rank_cache is None:
        import hashlib
        import socket

        from jax.experimental import multihost_utils

        host = int.from_bytes(
            hashlib.sha256(socket.gethostname().encode()).digest()[:8], "big"
        ) % (2**31)
        mine = jax.process_index()
        pairs = multihost_utils.process_allgather(
            np.asarray([host, mine], dtype=np.int64)
        ).reshape(-1, 2)
        _local_rank_cache = int(
            sum(1 for h, pid in pairs if h == host and pid < mine)
        )
    return _local_rank_cache


def is_primary() -> bool:
    """True on the process that owns logging/checkpoint-write duties
    (the reference's ``hvd.rank() == 0`` gates)."""
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Block until every process arrives (the reference's dummy-allreduce
    barrier, pytorch_cifar10_resnet.py:129-135)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    from kfac_pytorch_tpu.observability.telemetry import get_telemetry

    # span time here ≈ wait-for-slowest-host: the straggler gauge
    with get_telemetry().span("comm/barrier"):
        multihost_utils.sync_global_devices(name)


def host_min(value: int) -> int:
    """Minimum of a host-side int across all processes.

    For decisions every host must make IDENTICALLY (e.g. whether to use the
    native data pipeline — its shuffle RNG differs from the numpy one, so a
    per-host choice would silently break disjoint sharding).
    """
    if jax.process_count() == 1:
        return int(value)
    from jax.experimental import multihost_utils

    from kfac_pytorch_tpu.observability.telemetry import get_telemetry

    with get_telemetry().span("comm/host_min"):
        return int(
            np.min(multihost_utils.process_allgather(np.asarray(int(value))))
        )


def broadcast_host_value(value, root: int = 0):
    """Agree on a host-side Python value across processes (the reference's
    ``hvd.broadcast`` of the resume epoch, pytorch_imagenet_resnet.py:136-140).
    """
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    from kfac_pytorch_tpu.observability.telemetry import get_telemetry

    with get_telemetry().span("comm/broadcast"):
        arr = np.asarray(value)
        out = multihost_utils.broadcast_one_to_all(
            arr, is_source=jax.process_index() == root
        )
    return out.item() if np.ndim(value) == 0 else out
