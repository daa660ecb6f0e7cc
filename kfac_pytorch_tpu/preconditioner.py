"""KFAC: the distributed K-FAC gradient preconditioner (functional core).

The TPU-native re-design of the reference's ``KFAC(optim.Optimizer)``
(kfac_preconditioner.py:12-437). Where the reference mutates ``param.grad``
in place via hooks + Horovod allreduces, this version is a pure transform:

    kfac  = KFAC(...)
    state = kfac.init(params)
    new_grads, new_state = kfac.update(
        grads, state, a_contribs=..., g_factor_stats=...,
        lr=lr, damping=damping,
        update_factors=..., update_eigen=...)   # static flags

and chains in front of any SGD-like optimizer (optax). Key departures, all
deliberate (SURVEY.md §7):

* **No hooks** — statistics arrive explicitly from the capture machinery
  (models/layers.py + capture.py).
* **No factor allreduce** — A/G contributions are computed over the global
  (mesh-sharded) batch inside the jitted step, so XLA already inserted the
  mean-reduction the reference performs with ``hvd.allreduce(op=Average)``
  (kfac_preconditioner.py:410-419).
* **Step gating is host-side** — the trainer picks a step variant from the
  host-known step counter instead of tracing ``steps % freq`` branches; lr
  and damping stay traced scalars so schedulers never trigger recompiles.
* **Eigen state is rebuilt, not mutated** — so ``diag_blocks`` transitions
  need no ``_clear_eigen`` (kfac_preconditioner.py:167-178).
* **State is a checkpointable pytree** — unlike the reference, which loses
  all curvature state on resume (SURVEY.md §3.4 note).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from kfac_pytorch_tpu import capture, planner, shardwise
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.observability.telemetry import get_telemetry
from kfac_pytorch_tpu.ops import factor_kernels as factor_kernel_ops
from kfac_pytorch_tpu.ops import factors as factor_ops
from kfac_pytorch_tpu.ops import precondition as precond_ops
from kfac_pytorch_tpu.ops import streaming as streaming_ops
from kfac_pytorch_tpu.parallel.assignment import (
    layer_assignment,
    plan_eigh_chunks,
    plan_factor_shards,
    plan_owner_chunks,
    precondition_assignment,
    shard_plan_bytes,
)
from kfac_pytorch_tpu.parallel.comm import FactorComm
from kfac_pytorch_tpu.parallel.sharded_eigh import (
    build_slots,
    owner_eigen_chunk_update,
    owner_eigen_update,
    owner_spectrum_mass,
    owner_stream_fold,
    replicated_eigen_chunk_update,
    replicated_eigen_update,
    sharded_eigen_chunk_update,
    sharded_eigen_update,
)

PyTree = Any
KFACState = Dict[str, Any]


def _side_spectrum(e: Dict[str, jnp.ndarray], side: str) -> jnp.ndarray:
    """One side's eigenvalue spectrum for the health diagnostics. A truncated
    side's stored ``d`` covers only the captured subspace; appending its
    residual mass ``rho`` (the eigenvalue of every complement direction in
    the low-rank-plus-diagonal model) keeps min/max damped-eig and condition
    numbers meaningful — without it a well-conditioned truncated factor
    would read as having no small eigenvalues at all."""
    d = e[f"d{side}"]
    rho = e.get(f"rho{side}")
    if rho is None:
        return d
    return jnp.concatenate([d, jnp.reshape(rho, (1,)).astype(d.dtype)])


@dataclasses.dataclass
class KFACHParams:
    """Host-side mutable hyperparameters (the ``param_groups`` analog).

    ``KFACParamScheduler`` mutates these between epochs; ``damping`` enters
    the compiled step as a traced scalar, the update freqs drive host-side
    step-variant dispatch (kfac_preconditioner.py:351-356). ``lr`` is NOT
    stored here — the trainer's LR schedule is the single source of truth and
    every ``update()`` call must pass it (the reference equivalently re-reads
    lr from ``param_groups[0]`` that its ``LambdaLR`` maintains,
    kfac_preconditioner.py:351-356).
    """

    damping: float = 0.001
    kl_clip: float = 0.001
    fac_update_freq: int = 10
    kfac_update_freq: int = 100


def _validate(name: str, ok: bool, value) -> None:
    if not ok:
        raise ValueError(f"Invalid {name}: {value}")


def _non_tensor_world(mesh: Optional[Mesh], axis_name: str) -> int:
    """Replica count along the FACTOR plane: the product of every
    non-``tensor*`` mesh-axis size (``data`` × any ``fsdp*`` axes — both
    carry whole examples, so both carry factor contributions; see
    parallel/mesh.py::data_fsdp_tensor_mesh). ``tensor*`` replicas hold
    identical factor rows and are excluded."""
    if mesh is None:
        return 1
    if axis_name not in mesh.shape:
        return int(mesh.devices.size)
    world = 1
    for a in mesh.axis_names:
        if not str(a).startswith("tensor"):
            world *= int(mesh.shape[a])
    return world


# factor_comm_dtype spellings, and the names planner.Plan knows them by
_FACTOR_COMM_DTYPES = {
    "f32": jnp.float32,
    "float32": jnp.float32,
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    "int8": jnp.int8,
}
_PLAN_COMM_NAMES = {"float32": "f32", "bfloat16": "bf16"}


def _profile_facts(profile_shapes, layers):
    """``profile_shapes`` as the planner's ``ModelFacts``: already one, a
    plain ``{layer: (g_side, a_side)}`` dict, or a live params pytree whose
    sides are derived the way ``init`` will, honoring the layer list."""
    if profile_shapes is None or isinstance(profile_shapes, planner.ModelFacts):
        return profile_shapes
    d = dict(profile_shapes)
    if d and all(
        isinstance(v, (tuple, list)) and len(v) == 2
        and all(isinstance(s, (int, np.integer)) for s in v)
        for v in d.values()
    ):
        return planner.ModelFacts(
            shapes={k: (int(g), int(a)) for k, (g, a) in d.items()}
        )
    return planner.model_facts(profile_shapes, layers=layers)


class KFAC:
    """Distributed K-FAC gradient preconditioner.

    Args mirror the reference ``KFAC.__init__`` (kfac_preconditioner.py:59-91)
    with identical defaults and validation; ``mesh``/``axis_name`` replace the
    implicit Horovod world. ``lr`` is accepted and validated for reference
    API parity only — the lr the KL clip consumes is ALWAYS the per-step
    ``update(lr=...)`` argument (stored here as ``initial_lr``), exactly as
    the reference re-reads scheduler-maintained ``param_groups[0]['lr']``
    every step (kfac_preconditioner.py:351-356).
    """

    def __init__(
        self,
        lr: float = 0.1,
        factor_decay: float = 0.95,
        damping: float = 0.001,
        kl_clip: float = 0.001,
        fac_update_freq: int = 10,
        kfac_update_freq: int = 100,
        batch_averaged: bool = True,
        diag_blocks: int = 1,
        diag_warmup: int = 0,
        distribute_layer_factors: Optional[bool] = None,
        distribute_precondition: bool = False,
        precond_comm_dtype: Optional[Any] = None,
        mesh: Optional[Mesh] = None,
        axis_name: str = "data",
        eps: float = 1e-10,
        layers: Optional[list] = None,
        precond_precision: Optional[Any] = None,
        eigen_dtype: Any = jnp.float32,
        precond_method: str = "eigen",
        track_diagnostics: bool = False,
        eigh_chunks: int = 1,
        factor_kernel: str = "auto",
        factor_comm_dtype: Any = "f32",
        factor_comm_freq: int = 1,
        solver: str = "eigh",
        solver_rank: int = 128,
        solver_auto_threshold: int = 512,
        factor_sharding: str = "replicated",
        comm_overlap: bool = False,
        staleness_budget: int = 0,
        stream_drift_threshold: float = 0.05,
        service_devices: int = 0,
        profile: Optional[Any] = None,
        profile_shapes: Optional[Any] = None,
        shared_a: Optional[Dict[str, str]] = None,
    ):
        _validate("learning rate", 0.0 <= lr, lr)
        _validate("factor decay rate", 0.0 < factor_decay <= 1, factor_decay)
        _validate("damping", 0.0 < damping, damping)
        _validate("clipping value", 0.0 < kl_clip, kl_clip)
        _validate("factor update frequency", 0 < fac_update_freq, fac_update_freq)
        _validate("K-FAC update frequency", 0 < kfac_update_freq, kfac_update_freq)
        _validate("diagonal block approx count", 0 < diag_blocks, diag_blocks)
        if kfac_update_freq % fac_update_freq != 0:
            print(
                "WARNING: kfac_update_freq does not divide evenly by "
                "fac_update_freq; eigendecompositions will sometimes run on "
                "stale factors"
            )
        if diag_blocks != 1:
            print(
                "WARNING: the block-diagonal factor approximation "
                "(diag_blocks > 1) trades accuracy for parallelism — expect "
                "degraded convergence on some models"
            )

        self.initial_lr = lr  # parity/validation only; see class docstring
        self.factor_decay = factor_decay
        self.batch_averaged = batch_averaged
        self.diag_blocks = diag_blocks
        self.diag_warmup = diag_warmup
        self.distribute_layer_factors = distribute_layer_factors
        # Shard the EVERY-STEP eigenbasis rotations across the mesh (each
        # layer's triple-matmul chain runs on one owner device; one psum
        # reassembles). The reference replicates this work on every rank
        # (kfac_preconditioner.py:401-404) — fine when the per-rank SGD step
        # is ~90 ms (V100), a ~100% fixed tax when it is ~1.6 ms (v5e,
        # docs/PERF.md). Off by default: on 1-8 devices the psum can cost
        # more than the saved matmuls; enable at pod scale (the v5e-64
        # recipe), where per-device rotation work drops ~1/64.
        self.distribute_precondition = distribute_precondition
        # Wire-compression for the distributed-precondition exchange: cast
        # the psum'd updates to this dtype (e.g. jnp.bfloat16) and back —
        # the reference's Horovod fp16-allreduce compression
        # (pytorch_cifar10_resnet.py:190-195), applied to the one collective
        # this preconditioner issues explicitly. None = f32 (exact).
        if precond_comm_dtype is not None and not distribute_precondition:
            raise ValueError(
                "precond_comm_dtype compresses the distributed-precondition "
                "exchange and does nothing without distribute_precondition="
                "True — refusing a config whose numerics would silently "
                "change when run at scale"
            )
        self.precond_comm_dtype = precond_comm_dtype
        if distribute_precondition and (mesh is None or mesh.devices.size <= 1):
            # update() silently takes the replicated path in this case (and
            # precond_comm_dtype is then unused) — say so up front, mirroring
            # the precond_comm_dtype-without-distribute refusal above. Not an
            # error: trainers pass the same flags to 1-device dev runs.
            print(
                "WARNING: distribute_precondition=True has no effect without "
                "a multi-device mesh — preconditioning runs replicated"
                + (
                    " and precond_comm_dtype is unused"
                    if precond_comm_dtype is not None
                    else ""
                )
            )
        self.mesh = mesh
        self.axis_name = axis_name
        self.eps = eps
        # Explicit layer allowlist (from capture.discover_layers). None →
        # params heuristic; REQUIRED for models mixing in non-K-FAC
        # kernel-bearing modules (grouped convs, plain nn.Dense).
        self.layers = list(layers) if layers is not None else None
        # Shard-lens layer registry (kfac_pytorch_tpu/shardwise/): the
        # ``#c``/``#r``/``#e`` names capture.discover_layers emits for
        # tensor-sharded and MoE kernels. Only an explicit layers= list can
        # carry them (the params heuristic never synthesizes shard names),
        # so the named refusals below fire at construction, not mid-step.
        # Layers that read the same input as another keep no A factor of
        # their own: ``{layer: owner}``, both in ``layers``. The owner's A is
        # captured and averaged once; each layer still has its own inverse
        # (π is the layer's own). Inverse method, replicated state.
        self.shared_a = dict(shared_a or {})
        unknown = [
            n for pair in self.shared_a.items() for n in pair
            if n not in (self.layers or [])
        ]
        if unknown or set(self.shared_a) & set(self.shared_a.values()):
            raise ValueError(
                "shared_a maps layers of layers= to owners of layers= that "
                f"share with no one themselves; got {self.shared_a}"
            )
        self.shard_layers = shardwise.shard_entries(self.layers or [])
        self.has_shard_lens = shardwise.has_shard_lens(self.layers or [])
        self.has_moe = shardwise.has_moe(self.layers or [])
        # Precision of the every-step eigenbasis rotations (see
        # ops/precondition.py::_ROTATION_PRECISION for the default and why).
        # Accepts a lax.Precision or the strings 'default'/'high'/'highest'.
        if isinstance(precond_precision, str):
            from jax import lax

            precond_precision = {
                "default": lax.Precision.DEFAULT,
                "high": lax.Precision.HIGH,
                "highest": lax.Precision.HIGHEST,
            }[precond_precision.lower()]
        self.precond_precision = precond_precision
        # Storage dtype for the eigenVECTOR matrices (QA/QG) — the dominant
        # HBM stream of the every-step precondition path (~480 MB f32 read
        # twice per step on ResNet-50). bf16 halves that traffic; orthonormal
        # Q entries are O(1/√n) and well-conditioned, and eigenVALUES (the
        # damped divide) stay f32 regardless. Validated by the CIFAR
        # convergence runs (docs/PERF.md).
        self.eigen_dtype = eigen_dtype
        # "eigen" (reference parity: exact (G⊗A+λI)⁻¹ in the eigenbasis,
        # damping fresh every step, 4 rotations/layer) or "inverse"
        # (π-corrected factored Tikhonov damping + explicit inverses by a
        # sweep of matrix products: 2 matmuls/layer per step, half the
        # curvature HBM stream, ~10x less refresh arithmetic; damping takes
        # effect at the next refresh). See ops/precondition.py's
        # inverse-method comment.
        _validate(
            "precond_method", precond_method in ("eigen", "inverse"), precond_method
        )
        if precond_method == "inverse" and diag_blocks != 1:
            raise ValueError(
                "diag_blocks > 1 (and its diag_warmup schedule) is a feature "
                "of the eigenbasis path; precond_method='inverse' inverts "
                "whole factors and would silently ignore the configured "
                "block-diagonal approximation"
            )
        self.precond_method = precond_method
        # With expert banks ('#b' layers) or shared inputs the inverses live
        # in one table per side, refreshed where they lie
        # (ops/precondition.py, "Inverse tables"); every other model keeps
        # the per-layer / stacked layout.
        self.inverse_tables = bool(
            self.shared_a
            or any(
                capture.split_bank_name(n)[1] is not None
                for n in self.layers or []
            )
        )
        # Decoupled curvature service (kfac_pytorch_tpu/service/):
        # service_devices=N declares that N dedicated curvature workers were
        # carved OUT of the device set (split_service_mesh) and run the
        # eigen refresh out-of-band — this KFAC's mesh is the TRAINING
        # submesh and never sees them. In-step consequences: update()
        # structurally refuses every refresh flag (update_eigen /
        # eigen_chunk / swap_eigen), which is what pins the training-step
        # HLO to zero eigendecompositions; refreshed bases arrive via
        # service.ServiceClient.install between steps.
        _validate(
            "service_devices",
            isinstance(service_devices, int) and service_devices >= 0,
            service_devices,
        )
        # What the lever rules (planner/profiles.py::RULES) are judged
        # against besides the levers, read off this constructor's own
        # arguments. Other axes of size 1 split nothing and are left out;
        # owner shards split over the batch axes only (tensor* replicas hold
        # identical rows, parallel/mesh.py).
        facts = (
            _profile_facts(profile_shapes, self.layers)
            if profile is not None
            else None
        )
        env = planner.PlanEnv(
            world=1 if mesh is None else int(mesh.devices.size),
            data_world=_non_tensor_world(mesh, axis_name),
            mesh_axes=()
            if mesh is None
            else tuple(
                str(a)
                for a in mesh.axis_names
                if a == axis_name or int(mesh.shape[a]) > 1
            ),
            precond_method=precond_method,
            diag_blocks=diag_blocks,
            distribute_precondition=distribute_precondition,
            track_diagnostics=track_diagnostics,
            has_diag_a_layers=facts.has_diag_a if facts is not None else False,
            has_conv_layers=facts.has_conv if facts is not None else True,
            has_shard_lens_layers=self.has_shard_lens,
            has_moe_layers=self.has_moe,
            has_inverse_tables=self.inverse_tables,
            fac_update_freq=fac_update_freq,
            kfac_update_freq=kfac_update_freq,
            # the curvature-service carve the operator has OFFERED (the
            # devices already removed from this mesh by split_service_mesh);
            # the cost model decides engagement
            service_devices=service_devices,
        )
        levers = dict(
            eigh_chunks=eigh_chunks,
            factor_kernel=factor_kernel,
            factor_comm_dtype=factor_comm_dtype,
            factor_comm_freq=factor_comm_freq,
            solver=solver,
            solver_rank=solver_rank,
            solver_auto_threshold=solver_auto_threshold,
            factor_sharding=factor_sharding,
            comm_overlap=comm_overlap,
            staleness_budget=staleness_budget,
            stream_drift_threshold=stream_drift_threshold,
            service_devices=service_devices,
        )
        # profile=None is the bitwise-inert default: every lever keeps
        # exactly the value (explicit or default) the caller passed. A
        # profile name ("production"/"memory"/"safe") or a planner.Plan
        # resolves against the environment above and fills in ONLY the lever
        # arguments the caller left at their defaults — an explicit lever
        # always wins over the plan, so a profile is a starting point, not a
        # straitjacket (docs/PLANNER.md).
        self.plan = None
        self.plan_dropped: Tuple[str, ...] = ()
        self.plan_report = None
        self.plan_env = None
        if profile is not None:
            if isinstance(profile, planner.Plan):
                # An explicit plan must be valid as given; the degrade rules
                # then normalize it — e.g. owner sharding on a 1-device dev
                # run resolves to replicated, as the warning path below
                # would.
                planner.check_plan(profile, env)
                plan, dropped = planner.fit_plan(profile, env)
                report = None
            else:
                plan, report, dropped = planner.resolve_profile(
                    profile, facts, env
                )
            plan_defaults = planner.Plan()
            for field, value in plan.kfac_kwargs().items():
                if levers[field] == getattr(plan_defaults, field):
                    levers[field] = value
            self.plan = plan
            self.plan_dropped = tuple(dropped)
            self.plan_report = report
            self.plan_env = env
            planner.log_plan(plan, dropped)
        # Range checks of single lever values, then ONE check of how they
        # compose: every refusal that names two options is a row of
        # planner.RULES and raises from check_plan with the row's name.
        positive_int = lambda v: isinstance(v, int) and 0 < v  # noqa: E731
        for name, ok in (
            ("eigh_chunks", lambda v: 0 < v),
            ("solver", lambda v: v in ("eigh", "rsvd", "streaming")),
            ("solver_rank", positive_int),
            ("solver_auto_threshold", positive_int),
            ("factor_comm_freq", positive_int),
            (
                "stream_drift_threshold",
                lambda v: isinstance(v, (int, float)) and 0.0 <= float(v),
            ),
            ("factor_sharding", lambda v: v in ("replicated", "owner")),
            ("factor_kernel", lambda v: v in factor_kernel_ops.FACTOR_KERNELS),
            ("comm_overlap", lambda v: isinstance(v, bool)),
            ("staleness_budget", lambda v: isinstance(v, int) and v >= 0),
        ):
            _validate(name, ok(levers[name]), levers[name])
        comm_dtype = levers["factor_comm_dtype"]
        if isinstance(comm_dtype, str):
            _validate(
                "factor_comm_dtype",
                comm_dtype.lower() in _FACTOR_COMM_DTYPES,
                comm_dtype,
            )
            comm_dtype = _FACTOR_COMM_DTYPES[comm_dtype.lower()]
        comm_dtype = jnp.dtype(comm_dtype)
        # the levers as the caller ASKED for them: the rules fire on this,
        # even where a 1-device mesh degrades a lever below
        levers["factor_comm_dtype"] = _PLAN_COMM_NAMES.get(
            comm_dtype.name, comm_dtype.name
        )
        asked = planner.Plan(**levers)
        planner.check_plan(asked, env, enforced_by="constructor")
        # Pipelined curvature refresh: split the eigen refresh into this many
        # static chunks spread over the steps after each kfac_update_freq
        # boundary, double-buffered in state["eigen_pending"] and swapped in
        # atomically once every chunk lands (scheduler.EigenRefreshCadence
        # drives the cadence). 1 = today's monolithic refresh, bit-exact.
        self.eigh_chunks = int(asked.eigh_chunks)
        # Curvature solver for the refresh: "eigh" (full QDWH/syevd
        # eigendecomposition, reference parity, bitwise-inert default),
        # "rsvd" (randomized truncated eigensolve, ops/rsvd.py): factors with
        # side n ≥ solver_auto_threshold keep only their top solver_rank
        # eigenpairs plus a residual-trace diagonal, refresh via batched
        # matmuls instead of eigh custom-calls, and precondition through the
        # low-rank-plus-diagonal Woodbury path (ops/precondition.py), or
        # "streaming" (rsvd state layout, but the periodic refresh is
        # replaced by a per-capture-step matmul-only fold of the EMA'd
        # factors through the retained bases — ops/streaming.py; the full
        # rsvd refresh runs only as a re-orthonormalization when the
        # residual-mass drift gauge crosses stream_drift_threshold).
        # Factors below the threshold — or with solver_rank ≥ n, where
        # truncation buys nothing — stay on the dense path unchanged.
        self.solver = asked.solver
        self.stream_drift_threshold = float(asked.stream_drift_threshold)
        # Host-side drift source for the streaming re-orth decision: a
        # zero-arg callable returning the latest device residual-mass gauge
        # (trainers wire it to state["stream_residual"]). None → the cadence
        # re-orthonormalizes at every kfac_update_freq boundary, the safe
        # (and deterministic) degenerate schedule.
        self.stream_drift_signal = None
        self.solver_rank = int(asked.solver_rank)
        self.solver_auto_threshold = int(asked.solver_auto_threshold)
        # Where the factor running averages / eigenbases LIVE on the mesh:
        # "replicated" (default, bitwise-inert — every device holds every
        # layer's curvature state, reference parity) or "owner" (DP-KFAC,
        # arxiv 2206.15143: each layer's state lives only on its LPT
        # precondition owner; factor statistics reduce-SCATTER onto the
        # owner, the owner decomposes and solves locally, and one allgather
        # moves just the preconditioned gradients — per-replica state and
        # factor wire both become O(model/devices)). The shard layout is
        # parallel.assignment.plan_factor_shards.
        factor_sharding = asked.factor_sharding
        if factor_sharding == "owner":
            if env.multi_device and axis_name not in mesh.axis_names:
                # no row of RULES: the rules know the mesh's axes, not which
                # of them this KFAC was told carries the batch
                raise ValueError(
                    "factor_sharding='owner' requires a data-plane mesh: its "
                    f"shard stacks ride axis {axis_name!r}, and the mesh has "
                    f"axes {tuple(mesh.axis_names)}"
                )
            if env.data_world <= 1:
                # Mirrors the distribute_precondition warning: trainers pass
                # the same flags to 1-device dev runs. There is nothing to
                # shard across, so degrade to the (identical-numerics)
                # replicated layout instead of building 1-wide shards.
                print(
                    "WARNING: factor_sharding='owner' has no effect without "
                    "a multi-device mesh — factor state stays replicated"
                )
                factor_sharding = "replicated"
        self.factor_sharding = factor_sharding
        self._shard_plans: Dict[Any, Any] = {}
        self.service_devices = int(asked.service_devices)
        # Stability telemetry (costs two scalars of state + O(layers) mins):
        # ν — the KL trust-region coefficient actually applied each step
        # (kfac_preconditioner.py:320-326) — and the minimum damped
        # eigenvalue of any layer's (G ⊗ A + λI). A preconditioner-driven
        # divergence shows up here first: min eig → λ means a near-singular
        # curvature direction is being amplified by ~1/λ, and ν ≈ 1 means
        # the trust region is not catching it. Eigen method only (the
        # inverse method never materializes eigenvalues).
        self.track_diagnostics = track_diagnostics
        # Conv A-factor statistics kernel: "dense" is the im2col oracle
        # (ops/factors.py::compute_a_conv, kept verbatim), "pallas" the fused
        # patch-covariance kernel that never materializes the im2col tensor
        # (ops/factor_kernels.py — ~kh·kw× less factor-step HBM traffic, the
        # batch-128 lever of docs/PERF.md). "auto" resolves here, to dense on
        # every backend: the v5e compiler refuses the Pallas kernel at
        # ResNet-50 shapes (docs/PERF.md, "Refused by the v5e compiler"), so
        # it is an explicit opt-in that compiles or raises. Train steps open
        # a factor_kernel_scope with this value around their capture forward.
        self.factor_kernel = factor_kernel_ops.resolve_factor_kernel(
            asked.factor_kernel
        )
        # Overlap plane (the scheduling lever): comm_overlap=True issues the
        # factor-statistics bucket reductions interleaved with the gradient
        # pmean in the explicit shard_map wrapper (training/step.py), in
        # backward-layer order, so early-layer statistics cross the wire
        # while late-layer work is still in flight. psum results are
        # independent of issue position and bucket order, so the fused
        # stream is bitwise-identical to the serial one — it only changes
        # what the XLA scheduler may run concurrently.
        comm_overlap = asked.comm_overlap
        if comm_overlap and not env.multi_device:
            # Degrade, not refuse (planner rule overlap_vs_single_device):
            # trainers pass the same flags to 1-device dev runs, and there
            # is no cross-replica stream to fuse into.
            print(
                "WARNING: comm_overlap=True has no effect without a "
                "multi-device mesh — there is no factor exchange to overlap"
            )
            comm_overlap = False
        self.comm_overlap = comm_overlap
        # Batch-carrying reduction axes of the factor plane: the data axis
        # plus any size>1 fsdp* axes (parallel/mesh.py::data_fsdp_tensor_mesh
        # — fsdp replicas see whole examples, so their statistics reduce
        # alongside; PartitionSpec entries and lax collectives accept the
        # tuple transparently). A plain string on every pre-3-D mesh, so
        # existing programs are untouched.
        self.batch_axes: Any = axis_name
        if mesh is not None:
            _fsdp_axes = tuple(
                str(a)
                for a in mesh.axis_names
                if str(a).startswith("fsdp") and int(mesh.shape[a]) > 1
            )
            if _fsdp_axes:
                self.batch_axes = (axis_name,) + _fsdp_axes
        # Factor-communication plane (parallel/comm.py): bucketed fusion of
        # the per-layer A/G stat exchange, optional bf16 wire compression,
        # optional deferred reduction every `factor_comm_freq` capture steps
        # (flushed before every eigen refresh), optional int8 wire with
        # error-feedback residuals carried in state["wire_error"] on the
        # deferred path. Defaults are the parity escape hatch: f32 + freq 1
        # leaves the step's numerics bitwise unchanged, and without a
        # multi-device mesh the plane is inert.
        self.factor_comm = FactorComm(
            mesh=mesh,
            axis_name=self.batch_axes,
            comm_dtype=comm_dtype,
            comm_freq=asked.factor_comm_freq,
            sharded=self.owner_sharded,
            overlap=self.comm_overlap,
        )
        if (
            asked.factor_comm_freq > 1 or comm_dtype != jnp.dtype("float32")
        ) and not self.factor_comm.multi_device:
            # Mirrors the distribute_precondition warning above: not an
            # error — trainers pass the same flags to 1-device dev runs —
            # but the knobs shape a cross-replica exchange that does not
            # exist here, so say so up front.
            print(
                "WARNING: factor_comm_dtype/factor_comm_freq shape the "
                "cross-replica factor exchange and have no effect without a "
                "multi-device mesh= — factor statistics stay local and exact"
            )
        # Bounded-staleness budget: staleness_budget=S lets the cadence
        # (scheduler.EigenRefreshCadence) slip a deferred factor flush or a
        # pending eigen swap by up to S steps when the measured
        # comm/compute pressure says the wire is saturated. S=0 (default)
        # never slips — bitwise-inert.
        self.staleness_budget = int(asked.staleness_budget)
        # Host-side comm/compute pressure source for the slip decision:
        # a zero-arg callable returning the measured comm/compute ratio
        # (trainers wire one up from their timers). None → ratio 0 →
        # the cadence never slips, keeping replays (expected_step_variants)
        # and tests deterministic by default.
        self.staleness_signal = None
        self.hparams = KFACHParams(
            damping=damping,
            kl_clip=kl_clip,
            fac_update_freq=fac_update_freq,
            kfac_update_freq=kfac_update_freq,
        )

    # ------------------------------------------------------------------
    # Layer discovery
    # ------------------------------------------------------------------

    def _layer_meta(self, params: PyTree):
        names = self.layers if self.layers is not None else capture.layer_names(params)
        is_conv = {}
        for name in names:
            node = params
            # grouped ("path#gK") and lensed ("path#sK") pseudo-layers share
            # the base path's params
            for k in capture.layer_base(name).split("/"):
                node = node[k]
            # embedding layers (no "kernel" param) are neither conv nor dense
            is_conv[name] = "kernel" in node and node["kernel"].ndim == 4
        return names, is_conv

    def _rank_for(self, n: int) -> Optional[int]:
        """The single size→rank policy: the rank the randomized solver keeps
        for a factor side of size ``n``, or ``None`` for the dense path.

        ``solver_rank >= n`` falls back to dense — truncation would buy
        nothing, and keeping those sides dense makes ``r ≥ n`` configurations
        exactly bitwise-equal to ``solver="eigh"``. A pure function of the
        side size, so every slot in a shape bucket (and every host) derives
        the same answer; init(), the refresh planners, and the sharded
        updates all route through here.
        """
        if self.solver not in ("rsvd", "streaming"):
            return None
        if n < self.solver_auto_threshold or self.solver_rank >= n:
            return None
        return self.solver_rank

    def _rank_fn(self):
        """``rank_fn`` to thread into the refresh planners/updates: ``None``
        (not a function) when the solver is dense, so those paths stay
        bitwise-identical to the pre-solver code."""
        return (
            self._rank_for if self.solver in ("rsvd", "streaming") else None
        )

    def _spectrum_mass(
        self,
        facs: Dict[str, Dict[str, jnp.ndarray]],
        eigen_full: Dict[str, Dict[str, jnp.ndarray]],
        names,
    ) -> jnp.ndarray:
        """Fraction of total factor trace captured by the truncated bases.

        ``Σ d_r / Σ tr(F)`` summed over every low-rank factor side — the
        scalar behind the ``kfac/spectrum_mass_captured`` gauge. Near 1.0
        means the configured rank covers the curvature spectrum; a sagging
        value is the signal to raise ``solver_rank``. Exactly 1.0 when no
        side is truncated (nothing was discarded).
        """
        cap = jnp.zeros((), jnp.float32)
        tot = jnp.zeros((), jnp.float32)
        any_lr = False
        for n in names:
            e = eigen_full[n]
            for d_key, rho_key, f_key in (
                ("dA", "rhoA", "A"),
                ("dG", "rhoG", "G"),
            ):
                if rho_key not in e:
                    continue
                any_lr = True
                cap = cap + jnp.sum(e[d_key].astype(jnp.float32))
                tot = tot + jnp.trace(facs[n][f_key].astype(jnp.float32))
        if not any_lr:
            return jnp.ones((), jnp.float32)
        return cap / jnp.maximum(tot, 1e-30)

    def _world(self) -> int:
        # Eigendecomposition work shards over EVERY device of the mesh —
        # owners in the assignment table are flat device indices (row-major
        # over mesh.axis_names), matching the flat axis_index computed inside
        # sharded_eigen_update. A data×seq mesh therefore splits eigh work
        # across all devices rather than replicating per seq row.
        if self.mesh is None:
            return 1
        return int(self.mesh.devices.size)

    def _data_world(self) -> int:
        """Replica count along the FACTOR plane — what the owner shard plans
        size to. On a 2-D data×tensor mesh the shard stacks split over the
        data axis only (tensor replicas hold identical rows); on a 3-D
        data×fsdp×tensor mesh they split over data×fsdp (fsdp replicas see
        whole examples and carry their own factor rows) — unlike
        :meth:`_world`'s all-device eigh work-sharding."""
        return _non_tensor_world(self.mesh, self.axis_name)

    # ------------------------------------------------------------------
    # Owner sharding (factor_sharding="owner")
    # ------------------------------------------------------------------

    @property
    def owner_sharded(self) -> bool:
        return self.factor_sharding == "owner"

    def _shard_plan(
        self, shapes: Dict[str, Tuple[int, int]], diag_a=frozenset()
    ):
        """The owner-shard layout for this layer-shape set, cached.

        The plan is pure host-side configuration (every host derives the
        same one), so it compiles into the program; building it also lands
        the planned per-replica byte totals on the observability gauges —
        ``shard_plan_bytes`` is the same accounting bench reads, so the two
        cannot drift.
        """
        key = (
            tuple(sorted((n, tuple(s)) for n, s in shapes.items())),
            tuple(sorted(diag_a)),
        )
        plan = self._shard_plans.get(key)
        if plan is None:
            plan = plan_factor_shards(
                shapes,
                self._data_world(),
                self.factor_comm.max_bucket_elems,
                diag_a=set(diag_a),
            )
            self._shard_plans[key] = plan
            info = shard_plan_bytes(
                plan,
                rank_fn=self._rank_fn(),
                eigen_itemsize=jnp.dtype(self.eigen_dtype).itemsize,
            )
            tel = get_telemetry()
            tel.set_gauge(
                "kfac/factor_shard_bytes_local", info["total_buffer_local"]
            )
            tel.set_gauge(
                "kfac/factor_shard_owner_count", info["owner_count"]
            )
        return plan

    def state_shardings(self, state: KFACState) -> PyTree:
        """``NamedSharding`` pytree matching ``state`` — the placement
        contract of the owner mode.

        The ``*_shard`` stacks split their leading (world·rows) axis over
        the mesh axis; everything else (step counter, placeholder factor
        leaves, deferred local accumulators) is replicated. Callers must
        ``jax.device_put(state, kfac.state_shardings(state))`` before the
        first jitted step — ``init()`` already returns owner state placed
        this way — so pjit lays the shards out instead of inserting resharding
        collectives. Works for replicated-mode states too (everything P()).
        """
        if self.mesh is None:
            raise ValueError(
                "state_shardings() needs the KFAC mesh= to build "
                "NamedShardings against"
            )
        sharded_keys = ("factor_shard", "eigen_shard", "eigen_pending_shard")
        split = NamedSharding(self.mesh, P(self.batch_axes))
        full = NamedSharding(self.mesh, P())
        shard_entries = shardwise.shard_entries(list(state["factors"].keys()))
        out = {}
        for key, sub in state.items():
            if key in ("factors", "eigen") and shard_entries:
                # Shardwise layers place each factor/eigen block on the
                # device holding the matching kernel shard (column G-side
                # and row A-side stacks split over the tensor axis —
                # shardwise.factor_leaf_spec); everything else replicates.
                mapped = {}
                for name, entry in sub.items():
                    if name in shard_entries:
                        mapped[name] = {
                            k: NamedSharding(
                                self.mesh,
                                shardwise.factor_leaf_spec(
                                    name, k, tuple(v.shape), self.mesh
                                ),
                            )
                            for k, v in entry.items()
                        }
                    else:
                        mapped[name] = jax.tree_util.tree_map(
                            lambda _leaf: full, entry
                        )
                out[key] = mapped
                continue
            put = split if key in sharded_keys else full
            out[key] = jax.tree_util.tree_map(lambda _leaf, s=put: s, sub)
        return out

    def _owner_shapes(self, facs: Dict[str, Dict[str, jnp.ndarray]]):
        """Per-layer gradient-matrix shapes ``{name: (g, a)}`` plus the set
        of diagonal-A (embedding) layers, from full (replicated-form)
        factors — the key the shard plan is derived from, identical to what
        ``precondition_assignment`` sees at step time. Diagonal-A layers
        shard their [vocab] vector into the plan's ``v<size>`` groups."""
        shapes, diag = {}, set()
        for name, f in facs.items():
            if "A_diag" in f:
                shapes[name] = (
                    int(f["G"].shape[0]), int(f["A_diag"].shape[0])
                )
                diag.add(name)
            else:
                shapes[name] = (int(f["G"].shape[0]), int(f["A"].shape[0]))
        return shapes, diag

    def _owner_zero_eigen_shard(self, plan) -> Dict[str, Dict[str, jnp.ndarray]]:
        """Zero eigen-shard stacks (the owner analog of _eigen_side_init):
        one ``{"Q","d"[,"rho"]}`` stack per exact-size group, rows =
        world·rows_n, truncated groups shaped by the same size→rank policy
        as the replicated layout."""
        out = {}
        for n in plan.group_sizes:
            rows = plan.world * plan.group_rows[n]
            rank = self._rank_for(n)
            if rank is None:
                out[f"n{n}"] = {
                    "Q": jnp.zeros((rows, n, n), self.eigen_dtype),
                    "d": jnp.zeros((rows, n), jnp.float32),
                }
            else:
                out[f"n{n}"] = {
                    "Q": jnp.zeros((rows, n, rank), self.eigen_dtype),
                    "d": jnp.zeros((rows, rank), jnp.float32),
                    "rho": jnp.zeros((rows,), jnp.float32),
                }
        for n in plan.diag_group_sizes:
            # diagonal-A vector groups: the eigen entry is just the floored
            # diagonal — identity eigenvectors need no Q
            rows = plan.world * plan.diag_group_rows[n]
            out[f"v{n}"] = {"d": jnp.zeros((rows, n), jnp.float32)}
        return out

    def _owner_diag_eigen(self, shard, plan):
        """Refreshed eigen entries for the diagonal-A vector groups: the
        elementwise floor ``d·(d > eps)`` of the current factor shard — the
        owner twin of the replicated path's dA floor. O(vocab) elementwise on
        already-sharded stacks, so it runs at EVERY refresh/swap (no
        chunking, no pending buffer: the pending v entries stay zero and are
        overwritten here at promotion)."""
        return {
            f"v{n}": {
                "d": shard[f"v{n}"] * (shard[f"v{n}"] > self.eps)
            }
            for n in plan.diag_group_sizes
        }

    def _owner_factor_shard_from_full(
        self, facs: Dict[str, Dict[str, jnp.ndarray]], plan
    ) -> Dict[str, jnp.ndarray]:
        """Scatter full per-layer factors into the owner stacks (host-side:
        init's identity factors, or a replicated checkpoint being re-homed).
        Pad rows of under-loaded devices are zeros — fed only by the EMA
        decay, never read."""
        shard = {}
        for n in plan.group_sizes:
            rows = plan.group_rows[n]
            stack = np.zeros((plan.world * rows, n, n), np.float32)
            for s in plan.group_slots(n):
                stack[s.owner * rows + s.row] = np.asarray(
                    jax.device_get(facs[s.name][s.factor]), np.float32
                )
            shard[f"n{n}"] = jnp.asarray(stack)
        for n in plan.diag_group_sizes:
            rows = plan.diag_group_rows[n]
            stack = np.zeros((plan.world * rows, n), np.float32)
            for s in plan.group_slots(n, diag=True):
                stack[s.owner * rows + s.row] = np.asarray(
                    jax.device_get(facs[s.name]["A_diag"]), np.float32
                )
            shard[f"v{n}"] = jnp.asarray(stack)
        return shard

    def owner_state_from_replicated(self, state: KFACState) -> KFACState:
        """Re-home a replicated-mode state into the owner-sharded layout.

        The checkpoint migration path: restoring a replicated checkpoint
        with ``factor_sharding="owner"`` scatters each layer's factors and
        eigen entries into its owner's shard rows — deterministically, since
        the plan is a pure function of the layer shapes. Runs host-side
        (restore time, not step time). The eigen re-scatter preserves the
        stored bases bitwise; optional keys (pending buffers, sync age)
        carry over in owner form.
        """
        if not self.owner_sharded:
            raise ValueError(
                "owner_state_from_replicated() requires factor_sharding="
                "'owner'"
            )
        facs = state["factors"]
        shapes, diag_a = self._owner_shapes(facs)
        plan = self._shard_plan(shapes, frozenset(diag_a))
        full_eigen = self._eigen_entries_from_split(
            state["eigen"],
            state.get("eigen_stacked") or {},
            {n: s for n, s in shapes.items() if n not in diag_a},
        )
        eigen_shard = self._owner_eigen_shard_from_full(full_eigen, plan)
        new_state = {
            "step": state["step"],
            # placeholders keep the A_diag key for diagonal-A layers so the
            # step-time plan can re-derive the diag set from state alone
            "factors": {
                name: {("A_diag" if name in diag_a else "A"):
                       jnp.zeros((), jnp.float32),
                       "G": jnp.zeros((), jnp.float32)}
                for name in facs
            },
            "eigen": {},
            "eigen_stacked": {},
            "factor_shard": self._owner_factor_shard_from_full(facs, plan),
            "eigen_shard": eigen_shard,
        }
        if self.eigh_chunks > 1:
            pending = state.get("eigen_pending")
            if pending is not None:
                new_state["eigen_pending_shard"] = (
                    self._owner_eigen_shard_from_full(pending, plan)
                )
            else:
                new_state["eigen_pending_shard"] = jax.tree_util.tree_map(
                    jnp.zeros_like, eigen_shard
                )
        if self.solver in ("rsvd", "streaming"):
            new_state["spectrum_mass"] = state.get(
                "spectrum_mass", jnp.zeros((), jnp.float32)
            )
        if self.solver == "streaming":
            new_state["stream_residual"] = state.get(
                "stream_residual", jnp.zeros((), jnp.float32)
            )
            new_state["stream_fold_steps"] = state.get(
                "stream_fold_steps", jnp.zeros((), jnp.int32)
            )
        if self.factor_comm.defer:
            new_state["factor_local"] = {
                name: {
                    "A": jnp.zeros(
                        (shapes[name][1],) * (1 if name in diag_a else 2),
                        jnp.float32,
                    ),
                    "G": jnp.zeros((shapes[name][0],) * 2, jnp.float32),
                }
                for name in facs
            }
            # a replicated deferred state's factors may hold unmerged local
            # accumulators; the re-scatter treats them as synced (age 0) —
            # restore-time migration should come from a flushed checkpoint
            new_state["factor_sync_age"] = jnp.zeros((), jnp.int32)
        if self.staleness_budget > 0:
            new_state["eigen_swap_slip"] = state.get(
                "eigen_swap_slip", jnp.zeros((), jnp.int32)
            )
        return jax.device_put(new_state, self.state_shardings(new_state))

    def _eigen_entries_from_split(
        self,
        singles: Dict[str, Dict[str, jnp.ndarray]],
        stacked: Dict[str, Dict[str, jnp.ndarray]],
        shapes: Dict[str, Tuple[int, int]],
    ) -> Dict[str, Dict[str, jnp.ndarray]]:
        """Rebuild full per-layer eigen entries from the singles+stacked
        storage form (inverse of split_eigen_state, using the same
        shape_groups row-order contract)."""
        full = {n: dict(e) for n, e in singles.items()}
        for (g, a), names in precond_ops.shape_groups(shapes).items():
            key = f"{g}x{a}"
            if key in stacked:
                for i, n in enumerate(names):
                    full[n] = {k: v[i] for k, v in stacked[key].items()}
        return full

    def _owner_eigen_shard_from_full(
        self, eigen: Dict[str, Dict[str, jnp.ndarray]], plan
    ) -> Dict[str, Dict[str, jnp.ndarray]]:
        """Scatter full per-layer eigen entries into owner shard stacks
        (host-side twin of :meth:`_owner_factor_shard_from_full`)."""
        shard = self._owner_zero_eigen_shard(plan)
        out = {}
        for key, grp in shard.items():
            # np.array (not asarray): device_get returns read-only views
            host = {k: np.array(jax.device_get(v)) for k, v in grp.items()}
            n = int(key[1:])
            diag = key.startswith("v")
            rows = (plan.diag_group_rows if diag else plan.group_rows)[n]
            for s in plan.group_slots(n, diag):
                e = eigen[s.name]
                row = s.owner * rows + s.row
                if diag:
                    host["d"][row] = np.asarray(jax.device_get(e["dA"]))
                    continue
                host["Q"][row] = np.asarray(
                    jax.device_get(e[f"Q{s.factor}"])
                )
                host["d"][row] = np.asarray(
                    jax.device_get(e[f"d{s.factor}"])
                )
                if "rho" in host:
                    host["rho"][row] = np.asarray(
                        jax.device_get(e[f"rho{s.factor}"])
                    )
            out[key] = {
                k: jnp.asarray(v, grp[k].dtype) for k, v in host.items()
            }
        return out

    # ------------------------------------------------------------------
    # State
    # ------------------------------------------------------------------

    def _eigen_side_init(self, side: str, n: int) -> Dict[str, jnp.ndarray]:
        """Zero eigen-state entries for one factor side, shaped by the solver
        policy: dense sides get the square ``Q``/full ``d``; sides the
        randomized solver truncates (:meth:`_rank_for`) get rectangular
        ``[n, r]``/``[r]`` buffers plus the scalar residual mass — the state
        layout is fixed from init so refreshes never retrace the step."""
        rank = self._rank_for(n)
        if rank is None:
            return {
                f"Q{side}": jnp.zeros((n, n), self.eigen_dtype),
                f"d{side}": jnp.zeros((n,), jnp.float32),
            }
        return {
            f"Q{side}": jnp.zeros((n, rank), self.eigen_dtype),
            f"d{side}": jnp.zeros((rank,), jnp.float32),
            f"rho{side}": jnp.zeros((), jnp.float32),
        }

    def _identity_factors(
        self, params: PyTree
    ) -> Dict[str, Dict[str, jnp.ndarray]]:
        """Identity-initialized factor dict for ``params`` — the shape oracle.

        Factored out of :meth:`init` so restore-time machinery (the elastic
        replan path) can derive the per-layer factor shapes — and hence the
        deterministic owner-shard plan — from params alone, without building
        eigen state or touching a mesh.
        """
        names, _ = self._layer_meta(params)
        gcounts = capture.group_counts(names)
        scounts = capture.lens_counts(names)
        facs = {}
        for name in names:
            sbase, form, count = capture.split_shard_name(name)
            if form is not None:
                # shard-lens layer (#c/#r/#e): identity stacks shaped by the
                # sharding form (kfac_pytorch_tpu/shardwise/)
                node = params
                for k in sbase.split("/"):
                    node = node[k]
                facs[name] = shardwise.identity_factors(
                    form, count, tuple(node["kernel"].shape), "bias" in node
                )
                continue
            bbase, bcount = capture.split_bank_name(name)
            if bcount is not None:
                # expert bank: identity stacks [E, ., .], the dense layers'
                # init per expert
                node = params
                for k in bbase.split("/"):
                    node = node[k]
                _, a_in, m = node["kernel"].shape
                facs[name] = {
                    "A": jnp.broadcast_to(
                        jnp.eye(a_in, dtype=jnp.float32), (bcount, a_in, a_in)
                    ),
                    "G": jnp.broadcast_to(
                        jnp.eye(m, dtype=jnp.float32), (bcount, m, m)
                    ),
                }
                continue
            base, group_idx = capture.split_group_name(name)
            base, split_idx = capture.split_lens_name(base)
            node = params
            for k in base.split("/"):
                node = node[k]
            if "embedding" in node:
                # Diagonal-A (embedding) layer: A is a [vocab] vector whose
                # identity-init analog is all-ones (diag(I)); G is the usual
                # [features, features] matrix. Beyond-reference capability
                # (the reference's known_modules is {'Linear','Conv2d'},
                # kfac_preconditioner.py:103).
                vocab, feats = node["embedding"].shape
                facs[name] = {
                    "A_diag": jnp.ones((vocab,), jnp.float32),
                    "G": jnp.eye(feats, dtype=jnp.float32),
                }
                continue
            kernel = node["kernel"]
            has_bias = "bias" in node
            if kernel.ndim == 4:
                kh, kw, cin, cout = kernel.shape
                if group_idx is not None:
                    # grouped conv pseudo-layer: the HWIO I axis is already
                    # per-group; the O axis splits across the G groups
                    cout = cout // gcounts[base]
                a_side = cin * kh * kw + int(has_bias)
                g_side = cout
            else:
                cin, cout = kernel.shape
                if split_idx is not None:
                    # fused-projection lens pseudo-layer ("path#sK"): the
                    # shared input keeps the full A side; the O axis splits
                    # across the S column slices (expand setting,
                    # arxiv 2311.00636)
                    cout = cout // scounts[base]
                a_side = cin + int(has_bias)
                g_side = cout
            facs[name] = {
                "A": jnp.eye(a_side, dtype=jnp.float32),
                "G": jnp.eye(g_side, dtype=jnp.float32),
            }
        for name, owner in self.shared_a.items():
            if facs[name]["A"].shape != facs[owner]["A"].shape:
                raise ValueError(
                    f"shared_a: {name!r} and its owner {owner!r} read inputs "
                    f"of different shape ({facs[name]['A'].shape} against "
                    f"{facs[owner]['A'].shape})"
                )
        return facs

    def _inverse_layout(self, facs):
        """Where each layer's inverses lie in the inverse tables, from the
        factors' shapes (``facs`` may lack the ``A`` of a ``shared_a``
        layer): ops/precondition.py::inverse_table_layout."""
        shapes = {
            n: {k: tuple(v.shape) for k, v in f.items()} for n, f in facs.items()
        }
        return precond_ops.inverse_table_layout(shapes, self.shared_a)

    def factor_shapes(self, params: PyTree):
        """``({name: (g, a)}, diag_a_names)`` for ``params`` — the pure
        inputs of ``parallel.assignment`` planning. Every host derives the
        same answer from the same params structure, which is what makes the
        elastic resize replan deterministic."""
        return self._owner_shapes(self._identity_factors(params))

    def init(self, params: PyTree) -> KFACState:
        """Identity factors + zero eigen state (kfac_preconditioner.py:155-165).

        Identity init followed by the first EMA update reproduces the
        reference's ``steps == 0`` behavior (``A₀ = decay·I + (1−decay)·a``).
        """
        facs = self._identity_factors(params)
        eigen = {}
        for name, f in facs.items():
            _, form, _ = capture.split_shard_name(name)
            if form is not None:
                # shard-lens eigen entries carry FORM-PREFIXED keys
                # (cQA/rdG/…) so the singles/stacked split and the diag-A
                # detection leave them alone; always f32 (the stacks never
                # ride the eigen_dtype downcast — see shardwise/lenses.py)
                eigen[name] = shardwise.identity_eigen(form, f)
                continue
            if "A_diag" in f:
                vocab = int(f["A_diag"].shape[0])
                feats = int(f["G"].shape[0])
                if self.precond_method == "inverse":
                    eigen[name] = {
                        "iA_diag": jnp.zeros((vocab,), jnp.float32),
                        "iG": jnp.zeros((feats, feats), self.eigen_dtype),
                    }
                else:
                    eigen[name] = {
                        "dA": jnp.zeros((vocab,), jnp.float32),
                        **self._eigen_side_init("G", feats),
                    }
                continue
            a_side = int(f["A"].shape[0])
            g_side = int(f["G"].shape[0])
            if self.precond_method == "inverse":
                eigen[name] = {
                    "iA": jnp.zeros((a_side, a_side), self.eigen_dtype),
                    "iG": jnp.zeros((g_side, g_side), self.eigen_dtype),
                }
            else:
                eigen[name] = {
                    **self._eigen_side_init("A", a_side),
                    **self._eigen_side_init("G", g_side),
                }
        if self.inverse_tables:
            _, rows = self._inverse_layout(facs)
            # a layer that shares its input keeps no A of its own (shared_a)
            facs = {
                n: ({"G": f["G"]} if n in self.shared_a else f)
                for n, f in facs.items()
            }
            return {
                "step": jnp.zeros((), jnp.int32),
                "factors": facs,
                "eigen": {},
                "eigen_stacked": {},
                "inverse_tables": {
                    str(side): jnp.zeros((k, side, side), jnp.float32)
                    for side, k in rows.items()
                },
            }
        if self.owner_sharded:
            return self._owner_init(facs)
        # same-shape groups live ONLY pre-stacked (batched-rotation form);
        # singleton shapes stay per-layer — see split_eigen_state
        if self.precond_method == "inverse":
            singles, stacked = precond_ops.split_inv_state(eigen)
        else:
            singles, stacked = precond_ops.split_eigen_state(eigen)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "factors": facs,
            "eigen": singles,
            "eigen_stacked": stacked,
        }
        if self.eigh_chunks > 1:
            # Double buffer for the pipelined refresh: the accumulating
            # eigenbasis in FULL per-layer form (chunks scatter block
            # regions; the swap step re-splits into singles+stacked). Fixed
            # from init — chunks=1 states carry no pending buffer, so the
            # monolithic configuration's pytree (and checkpoints) are
            # untouched.
            state["eigen_pending"] = {n: dict(e) for n, e in eigen.items()}
        if self.solver in ("rsvd", "streaming"):
            # Fraction of total factor trace the truncated bases captured at
            # the last refresh (1.0 when no side crossed the threshold) —
            # the in-graph source of the kfac/spectrum_mass_captured gauge.
            # Fixed from init like the other optional state keys.
            state["spectrum_mass"] = jnp.zeros((), jnp.float32)
        if self.solver == "streaming":
            # Streaming drift bookkeeping: the residual-mass gauge the fold
            # writes each capture step (the device source of the
            # kfac/stream_residual_mass gauge and the host drift signal) and
            # the count of folds since the last re-orthonormalization. Fixed
            # from init like the other optional state keys.
            state["stream_residual"] = jnp.zeros((), jnp.float32)
            state["stream_fold_steps"] = jnp.zeros((), jnp.int32)
        if self.factor_comm.defer:
            # Deferred factor communication: the factor running averages
            # double as per-replica LOCAL accumulators between flushes (no
            # extra buffers — the EMA's linearity makes the flush-time mean
            # of local EMAs exact, see ops.factors.merge_running_avg_buckets).
            # This counter tracks capture steps since the last cross-replica
            # merge (0 == globally synced); fixed from init so the state
            # pytree structure never changes mid-run.
            state["factor_sync_age"] = jnp.zeros((), jnp.int32)
            if self.factor_comm.quantized:
                # Int8 wire error feedback: one f32 residual buffer per wire
                # bucket, carrying what this replica's last quantized flush
                # rounded away (folded into the next payload —
                # parallel/comm.py::FactorComm._merge_quantized). PER-REPLICA
                # DIVERGENT data in replicated-annotation arrays, exactly
                # like the deferred factors themselves; elastic/state_io.py
                # packs them per replica for snapshots. Fixed from init.
                state["wire_error"] = self.factor_comm.wire_error_init(facs)
        if self.staleness_budget > 0:
            # Bounded-staleness bookkeeping: 1 while a fully-landed pending
            # eigenbasis is waiting for its (slipped) swap, else 0. The slip
            # DEPTH is host-side cadence state (kfac/eigen_swap_slip gauge);
            # this in-state flag is what checkpoints/tests read. Fixed from
            # init like the other optional keys.
            state["eigen_swap_slip"] = jnp.zeros((), jnp.int32)
        if self.track_diagnostics:
            # fixed from init so the state pytree structure never changes
            # (a mid-run structure flip would retrace the jitted step and
            # break checkpoint/donation contracts). Key vocabulary:
            # observability/diagnostics.py; semantics: docs/OBSERVABILITY.md.
            state["diagnostics"] = {
                "nu": jnp.ones((), jnp.float32),
                "min_damped_eig": jnp.zeros((), jnp.float32),
                "max_damped_eig": jnp.zeros((), jnp.float32),
                "grad_norm": jnp.zeros((), jnp.float32),
                "update_norm": jnp.zeros((), jnp.float32),
                "update_grad_cos": jnp.zeros((), jnp.float32),
                "eigen_stale_steps": jnp.zeros((), jnp.int32),
                "layer_cond": {
                    name: {
                        "cond_A": jnp.zeros((), jnp.float32),
                        "cond_G": jnp.zeros((), jnp.float32),
                    }
                    for name in facs
                },
            }
        return state

    def _owner_init(self, facs: Dict[str, Dict[str, jnp.ndarray]]) -> KFACState:
        """Owner-sharded initial state from init()'s identity factors.

        The pytree layout the owner mode fixes from init: per-layer
        ``factors`` shrink to scalar-zero placeholders (the name registry —
        scalars, not zero-size arrays, so orbax checkpoints them —
        the layer SET stays readable from state, and the pytree structure
        is mesh-uniform for pjit), curvature lives in the ``factor_shard``/
        ``eigen_shard`` stacks sharded over the mesh axis, deferred mode
        adds the full-size per-replica local accumulator + sync-age counter,
        and ``eigh_chunks > 1`` adds the sharded pending double buffer.
        Returned already placed per :meth:`state_shardings`.
        """
        shapes, diag_a = self._owner_shapes(facs)
        plan = self._shard_plan(shapes, frozenset(diag_a))
        eigen_shard = self._owner_zero_eigen_shard(plan)
        state = {
            "step": jnp.zeros((), jnp.int32),
            # diagonal-A layers keep their A_diag placeholder KEY so the
            # step-time plan re-derives the diag set from state alone
            "factors": {
                name: {("A_diag" if name in diag_a else "A"):
                       jnp.zeros((), jnp.float32),
                       "G": jnp.zeros((), jnp.float32)}
                for name in facs
            },
            "eigen": {},
            "eigen_stacked": {},
            "factor_shard": self._owner_factor_shard_from_full(facs, plan),
            "eigen_shard": eigen_shard,
        }
        if self.eigh_chunks > 1:
            state["eigen_pending_shard"] = jax.tree_util.tree_map(
                jnp.zeros_like, eigen_shard
            )
        if self.solver in ("rsvd", "streaming"):
            state["spectrum_mass"] = jnp.zeros((), jnp.float32)
        if self.solver == "streaming":
            state["stream_residual"] = jnp.zeros((), jnp.float32)
            state["stream_fold_steps"] = jnp.zeros((), jnp.int32)
        if self.factor_comm.defer:
            # Deferred owner mode: unlike the replicated plane (where the
            # factors themselves double as local accumulators), non-owners
            # hold no master EMA — so the between-flush accumulation needs
            # its own full-size per-replica buffer, zeroed at every flush.
            state["factor_local"] = {
                name: {
                    "A": jnp.zeros(
                        (shapes[name][1],) * (1 if name in diag_a else 2),
                        jnp.float32,
                    ),
                    "G": jnp.zeros((shapes[name][0],) * 2, jnp.float32),
                }
                for name in facs
            }
            state["factor_sync_age"] = jnp.zeros((), jnp.int32)
        if self.staleness_budget > 0:
            state["eigen_swap_slip"] = jnp.zeros((), jnp.int32)
        return jax.device_put(state, self.state_shardings(state))

    # ------------------------------------------------------------------
    # Update
    # ------------------------------------------------------------------

    def update(
        self,
        grads: PyTree,
        state: KFACState,
        *,
        a_contribs: Optional[Dict[str, jnp.ndarray]] = None,
        g_factor_stats: Optional[Dict[str, jnp.ndarray]] = None,
        bank_tape: Optional[Dict[str, Tuple[jnp.ndarray, ...]]] = None,
        grad_scale: Optional[jnp.ndarray] = None,
        lr: Optional[jnp.ndarray] = None,
        damping: Optional[jnp.ndarray] = None,
        update_factors: bool,
        update_eigen: bool,
        diag_warmup_done: bool = True,
        eigen_chunk: Optional[Tuple[int, int]] = None,
        swap_eigen: bool = False,
        flush_factors: bool = False,
    ) -> Tuple[PyTree, KFACState]:
        """One K-FAC step (kfac_preconditioner.py:336-408), functional.

        ``update_factors``/``update_eigen``/``diag_warmup_done`` are STATIC —
        the trainer derives them host-side from the step counter and epoch
        (see ``training.step.kfac_flags_for_step``); each combination is its
        own compiled program, so non-update steps pay zero capture/eigh cost.
        ``a_contribs``/``g_factor_stats`` come from capture.py and are
        required iff ``update_factors``. ``bank_tape`` (capture.py::bank_tape:
        ``{bank: (rows, cotangents, group sizes)}``, from a step whose
        gradient they make) lets the apply precondition an expert bank from
        the rows each expert saw (ops/precondition.py::precondition_bank_rows)
        wherever that costs no more than the dense form, which is a rule of
        shapes alone; ``grad_scale`` is the one factor by which the step
        scaled the gradients since (the global-norm clip), None for 1.
        ``lr`` is REQUIRED (it scales the KL
        trust-region clip, kfac_preconditioner.py:320-326, and must track the
        trainer's schedule — a silently-stale fallback here once meant the
        clip used the construction-time lr). ``damping`` defaults to the
        scheduler-maintained ``hparams.damping``; pass both as traced scalars
        so schedules never recompile.

        ``eigen_chunk``/``swap_eigen`` (STATIC, ``eigh_chunks > 1`` only)
        drive the pipelined refresh: ``eigen_chunk=(c, k)`` runs chunk ``c``
        of a ``k``-chunk plan into ``state["eigen_pending"]`` — this step
        still preconditions with the ACTIVE basis — and ``swap_eigen=True``
        on the final chunk's step promotes the completed pending basis
        before preconditioning (the atomic swap). The cadence — including
        the never-swap-a-partial-basis invariant — lives in
        ``scheduler.EigenRefreshCadence``; callers should not hand-roll it.

        ``flush_factors`` (STATIC, deferred factor communication only, i.e.
        ``factor_comm_freq > 1`` on a multi-device mesh) merges the
        per-replica locally-accumulated factor running averages across the
        mesh — after this step's EMA, before any eigen work reads them. The
        cadence helpers set it every ``factor_comm_freq``-th capture step
        and on every step that starts an eigen refresh; ``update()`` refuses
        a refresh that would read unmerged local factors.
        """
        if lr is None:
            raise ValueError(
                "KFAC.update() requires lr= (the KL clip scales with the "
                "trainer's current learning rate)"
            )
        if damping is None:
            damping = self.hparams.damping
        if self.service_devices > 0 and (
            update_eigen or eigen_chunk is not None or swap_eigen
        ):
            # This refusal IS the zero-eigh training-HLO guarantee the
            # service mode advertises (scripts/check_service_hlo.py): no
            # flag combination can trace a refresh into the training step.
            raise ValueError(
                "service_devices > 0 delegates the curvature refresh to "
                "dedicated workers — the training step must never run "
                "update_eigen/eigen_chunk/swap_eigen; refreshed bases "
                "arrive via service.ServiceClient.install between steps"
            )
        if eigen_chunk is not None:
            if self.eigh_chunks <= 1:
                raise ValueError(
                    "eigen_chunk= requires KFAC(eigh_chunks > 1) — the state "
                    "carries no eigen_pending double buffer to accumulate into"
                )
            if update_eigen:
                raise ValueError(
                    "eigen_chunk= and update_eigen=True are mutually "
                    "exclusive: a step either pipelines one chunk or runs "
                    "the monolithic refresh"
                )
            c, k = eigen_chunk
            if not (0 < k and 0 <= c < k):
                raise ValueError(f"Invalid eigen_chunk: {eigen_chunk}")
        elif swap_eigen:
            # The bare-swap catch-up variant: a slipped swap (bounded
            # staleness) lands on a later step that runs no chunk — only
            # legal when a budget licenses the slip; without one the swap
            # must ride the final chunk's step so the program count stays
            # bounded.
            if self.staleness_budget <= 0:
                raise ValueError(
                    "swap_eigen=True without eigen_chunk=: the swap rides "
                    "the final chunk's step so the program count stays "
                    "bounded (only a staleness_budget > 0 configuration "
                    "may land a slipped swap on a chunk-free step)"
                )
            if self.eigh_chunks <= 1:
                raise ValueError(
                    "swap_eigen=True requires KFAC(eigh_chunks > 1) — the "
                    "state carries no eigen_pending double buffer to promote"
                )
            if update_eigen:
                raise ValueError(
                    "swap_eigen= and update_eigen=True are mutually "
                    "exclusive: the monolithic refresh installs its own "
                    "basis"
                )
        if flush_factors and not self.factor_comm.defer:
            raise ValueError(
                "flush_factors=True without deferred factor communication "
                "(factor_comm_freq > 1 on a multi-device mesh) — there is "
                "no locally-accumulated factor state to merge"
            )
        if self.factor_comm.defer and not flush_factors:
            if update_eigen or (eigen_chunk is not None and eigen_chunk[0] == 0):
                raise ValueError(
                    "deferred factor communication requires flush_factors="
                    "True on every step that starts an eigen refresh — the "
                    "eigendecomposition would otherwise read per-replica "
                    "unmerged factors. The cadence helpers "
                    "(kfac_flags_for_step / EigenRefreshCadence) set this; "
                    "hand-rolled schedules must too."
                )
        if self.owner_sharded:
            return self._update_owner(
                grads,
                state,
                a_contribs=a_contribs,
                g_factor_stats=g_factor_stats,
                lr=lr,
                damping=damping,
                update_factors=update_factors,
                update_eigen=update_eigen,
                eigen_chunk=eigen_chunk,
                swap_eigen=swap_eigen,
                flush_factors=flush_factors,
            )
        # The layer set was fixed at init() — state IS the source of truth,
        # so a heuristic/params mismatch cannot silently widen the set here.
        names = list(state["factors"].keys())
        # shard-lens layers (#c/#r/#e) branch out of the generic EMA /
        # refresh / precondition flows below (kfac_pytorch_tpu/shardwise/)
        shard_items = shardwise.shard_entries(names)
        norm_names = [n for n in names if n not in shard_items]
        is_conv = {}
        for name in names:
            node = grads
            # grouped ("path#gK") and lensed ("path#sK") pseudo-layers share
            # the base path's grads
            for k in capture.layer_base(name).split("/"):
                node = node[k]
            is_conv[name] = "kernel" in node and node["kernel"].ndim == 4

        # phase() marks each K-FAC phase on both clocks (observability/
        # phases.py): a named scope on the ops traced inside, from which a
        # device trace is split by phase (observability/device_phases.py),
        # and a telemetry span that runs at TRACE time (update() executes
        # inside jit) and measures per-phase tracing cost. Neither emits an
        # op into the program (docs/OBSERVABILITY.md).

        facs = state["factors"]
        if update_factors:
            if a_contribs is None or g_factor_stats is None:
                raise ValueError(
                    "update_factors=True requires a_contribs and g_factor_stats"
                )
            missing = [
                n for n in names
                if (n not in a_contribs and n not in self.shared_a)
                or n not in g_factor_stats
            ]
            if missing:
                raise ValueError(
                    f"no captured statistics for layers {missing}; the model "
                    "contains kernel-bearing modules that are not K-FAC "
                    "capture-aware — construct KFAC(layers=capture."
                    "discover_layers(model, ...)) so init() matches capture."
                )
            # EMA runs elementwise, so the same update serves dense A
            # matrices, embedding A_diag vectors (identity init = ones), and
            # the column/row shard stacks (update_running_avg broadcasts
            # over the stack dim). Only MoE diverges: its token-count-
            # weighted per-expert decay routes through shardwise.ema_update.
            with phase("kfac_capture", "trace/kfac/factor_update"):
                old_facs = facs
                facs = {}
                for name in names:
                    se = shard_items.get(name)
                    if se is not None:
                        facs[name] = shardwise.ema_update(
                            se[1],
                            old_facs[name],
                            a_contribs[name],
                            g_factor_stats[name],
                            self.factor_decay,
                        )
                        continue
                    if name in self.shared_a:  # the owner averages the A
                        facs[name] = {
                            "G": factor_ops.update_running_avg(
                                g_factor_stats[name],
                                old_facs[name]["G"],
                                self.factor_decay,
                            )
                        }
                        continue
                    facs[name] = {
                        ("A_diag" if "A_diag" in old_facs[name] else "A"):
                            factor_ops.update_running_avg(
                                a_contribs[name],
                                old_facs[name].get(
                                    "A", old_facs[name].get("A_diag")
                                ),
                                self.factor_decay,
                            ),
                        "G": factor_ops.update_running_avg(
                            g_factor_stats[name],
                            old_facs[name]["G"],
                            self.factor_decay,
                        ),
                    }
        wire_error = state.get("wire_error")
        if flush_factors:
            # Deferred-mode merge of the per-replica running averages —
            # AFTER this step's EMA (so the flush includes it), BEFORE any
            # eigen path below reads the factors.
            if self.factor_comm.quantized:
                # int8 wire: fold in / carry out the error-feedback
                # residuals; the step counter keys the deterministic
                # stochastic rounding.
                facs, wire_error = self.factor_comm.flush(
                    facs, wire_error=wire_error, seed=state["step"]
                )
            else:
                facs = self.factor_comm.flush(facs)

        eigen = state["eigen"]
        stacked = state.get("eigen_stacked")
        tables = state.get("inverse_tables")
        pending = state.get("eigen_pending")
        spectrum_mass = state.get("spectrum_mass")
        # Per-layer eigenvalue spectra captured (pre-split) on eigen-update
        # steps for the health diagnostics; None on every other path.
        fresh_spectra = None

        # Overlap plane, mechanism (b): on a chunk-only step the chunk
        # feeds ONLY the pending double buffer — nothing the preconditioned
        # gradients read — so emit the precondition FIRST. The traced values
        # are identical either way (pure dataflow); what changes is program
        # order, which keeps the gradient outputs off the chunk-eigh's
        # critical path so async dispatch can overlap chunk k with step
        # k+1's backprop. Gated on comm_overlap so the default emission
        # order (and HLO) is untouched.
        precond_early = (
            self.comm_overlap and eigen_chunk is not None and not swap_eigen
        )
        if precond_early:
            with phase("kfac_apply", "trace/kfac/precondition"):
                new_grads, gmats, updates, nu = self._precondition_replicated(
                    grads, names, facs, eigen, stacked, lr, damping
                )

        if update_eigen and self.precond_method == "inverse":
            # Curvature refresh, inverse method: π-damped inverses by a
            # blocked sweep of matrix products (ops/precondition.py::
            # _spd_inverse_stack). Computed replicated — n³ multiply-adds a
            # factor against the eigendecompositions' ~10n³, so at
            # kfac_update_freq amortization sharding it is not worth an
            # exchange; the EVERY-STEP solve still shards via
            # distribute_precondition.
            with phase("kfac_refresh", "trace/kfac/eigh"):
                if self.inverse_tables:
                    tables = precond_ops.factored_inverse_tables(
                        facs, tables, jnp.asarray(damping, jnp.float32),
                        self.eps, self.shared_a,
                        self._inverse_layout(facs)[0],
                    )
                    inv = {}
                else:
                    inv = precond_ops.factored_inverse_all(
                        facs, jnp.asarray(damping, jnp.float32), self.eps
                    )
                if self.eigen_dtype != jnp.float32:
                    inv = {
                        # only the MATRIX inverses downcast; the embedding
                        # iA_diag vector stays f32 like the eigen path's dA
                        # (a dtype flip after the first refresh would retrace
                        # the jitted step and break donated-buffer reuse)
                        n: {
                            k: (v if k == "iA_diag" else v.astype(self.eigen_dtype))
                            for k, v in e.items()
                        }
                        for n, e in inv.items()
                    }
                eigen, stacked = precond_ops.split_inv_state(inv)
        elif update_eigen:
            # diag_warmup: use 1 block until `epoch >= diag_warmup`
            # (kfac_preconditioner.py:361-367), via the static flag.
            diag_blocks = self.diag_blocks if diag_warmup_done else 1
            world = self._world()
            norm_facs = {n: facs[n] for n in norm_names}
            with phase("kfac_refresh", "trace/kfac/eigh"):
                if not norm_facs:
                    eigen = {}
                elif world > 1:
                    table = layer_assignment(
                        norm_names,
                        is_conv,
                        world,
                        self.distribute_layer_factors,
                        diag_blocks,
                    )
                    eigen = sharded_eigen_update(
                        norm_facs, table, self.mesh, self.axis_name, self.eps,
                        rank_fn=self._rank_fn(),
                    )
                else:
                    blocks = {
                        name: (diag_blocks if is_conv[name] else 1)
                        for name in norm_names
                    }
                    eigen = replicated_eigen_update(
                        norm_facs, blocks, self.eps, rank_fn=self._rank_fn()
                    )
                # Shard-lens layers: per-block dense eigh, batched over the
                # stack dim, replicated on every device holding the block
                # (shardwise/lenses.py) — no assignment table, no collective.
                for n, (_, form, _) in shard_items.items():
                    eigen[n] = shardwise.eigen_refresh(form, facs[n])
                # Diagonal-A (embedding) layers: the A "eigendecomposition" is
                # the diagonal itself (eigenvectors = identity) — no eigh, just
                # the reference's eigenvalue floor (kfac_preconditioner.py:253).
                for n in norm_names:
                    if "A_diag" in facs[n]:
                        d = facs[n]["A_diag"]
                        eigen[n]["dA"] = d * (d > self.eps)
                if self.solver in ("rsvd", "streaming"):
                    spectrum_mass = self._spectrum_mass(
                        facs, eigen, norm_names
                    )
                if self.track_diagnostics:
                    # grab the f32 per-layer spectra while the eigen dict is
                    # still in full per-layer form (stacks lose layer keys);
                    # shard entries contribute their flattened per-block
                    # spectra so the diagnostics pytree keeps every layer
                    fresh_spectra = {}
                    for n in names:
                        se = shard_items.get(n)
                        if se is not None:
                            _, da_k, _, dg_k = shardwise.EIGEN_KEYS[se[1]]
                            fresh_spectra[n] = (
                                eigen[n][da_k].reshape(-1),
                                eigen[n][dg_k].reshape(-1),
                            )
                        else:
                            fresh_spectra[n] = (
                                _side_spectrum(eigen[n], "A"),
                                _side_spectrum(eigen[n], "G"),
                            )
                if self.eigen_dtype != jnp.float32:
                    # eigh itself always runs f32; only the stored/streamed Q
                    # matrices downcast (eigenvalues stay f32 for the divide)
                    eigen = {
                        n: {
                            k: (v.astype(self.eigen_dtype) if k.startswith("Q") else v)
                            for k, v in e.items()
                        }
                        for n, e in eigen.items()
                    }
                eigen, stacked = precond_ops.split_eigen_state(eigen)
        elif eigen_chunk is not None:
            # Pipelined refresh: run this step's chunk of the eigh plan on
            # the CURRENT factors into the pending double buffer. The plan is
            # host-side static (deterministic LPT over the same slot set the
            # monolithic refresh would build), so the chunk id selects a
            # bounded set of compiled programs — one per (chunk, factors)
            # combination — instead of retracing per layer.
            c, k = eigen_chunk
            diag_blocks = self.diag_blocks if diag_warmup_done else 1
            world = self._world()
            if world > 1:
                table = layer_assignment(
                    names,
                    is_conv,
                    world,
                    self.distribute_layer_factors,
                    diag_blocks,
                )
                slots = build_slots(facs, table)
            else:
                blocks = {
                    name: (diag_blocks if is_conv[name] else 1) for name in names
                }
                slots = build_slots(facs, None, blocks)
            chunk_slots = [
                slots[i]
                for i in plan_eigh_chunks(slots, k, rank_fn=self._rank_fn())[c]
            ]
            if c == 0:
                # Fresh interval: zero the whole double buffer so the swap
                # sees exactly what a from-zeros _assemble would build —
                # off-block regions must not inherit a previous interval's
                # values when diag_blocks (warmup) shifts block boundaries.
                pending = jax.tree_util.tree_map(jnp.zeros_like, pending)
            with phase("kfac_refresh", "trace/kfac/eigh"):
                if chunk_slots:
                    if world > 1:
                        pending = sharded_eigen_chunk_update(
                            facs, pending, chunk_slots, self.mesh, self.eps,
                            rank_fn=self._rank_fn(),
                        )
                    else:
                        pending = replicated_eigen_chunk_update(
                            facs, pending, chunk_slots, self.eps,
                            rank_fn=self._rank_fn(),
                        )
            if swap_eigen:
                # Atomic swap: every chunk has landed (EigenRefreshCadence
                # guarantees it), so promote the pending basis and
                # precondition THIS step with it — the pipelined analog of
                # the monolithic refresh step. Embedding diagonal-A layers
                # never go through eigh; their floored diagonal comes from
                # the current factors exactly as the monolithic path does.
                full = {n: dict(e) for n, e in pending.items()}
                for n in names:
                    if "A_diag" in facs[n]:
                        d = facs[n]["A_diag"]
                        full[n]["dA"] = d * (d > self.eps)
                if self.solver == "rsvd":
                    spectrum_mass = self._spectrum_mass(facs, full, names)
                if self.track_diagnostics:
                    fresh_spectra = {
                        n: (
                            _side_spectrum(full[n], "A"),
                            _side_spectrum(full[n], "G"),
                        )
                        for n in names
                    }
                eigen, stacked = precond_ops.split_eigen_state(full)
        elif swap_eigen:
            # Bare-swap catch-up (bounded staleness): a swap that slipped
            # past its final-chunk step lands here — every chunk is in the
            # pending buffer already, so just promote it, exactly as the
            # riding-swap branch above does, without running any chunk.
            full = {n: dict(e) for n, e in pending.items()}
            for n in names:
                if "A_diag" in facs[n]:
                    d = facs[n]["A_diag"]
                    full[n]["dA"] = d * (d > self.eps)
            if self.solver == "rsvd":
                spectrum_mass = self._spectrum_mass(facs, full, names)
            if self.track_diagnostics:
                fresh_spectra = {
                    n: (
                        _side_spectrum(full[n], "A"),
                        _side_spectrum(full[n], "G"),
                    )
                    for n in names
                }
            eigen, stacked = precond_ops.split_eigen_state(full)

        # Streaming curvature (solver="streaming"): capture steps fold the
        # freshly EMA'd (and, in deferred mode, freshly merged) factors
        # through the retained bases — matmul-only d/rho rebuild plus the
        # residual-mass drift gauge (ops/streaming.py). Re-orthonormalization
        # steps are plain update_eigen refreshes (handled above); they reset
        # the gauge from the refresh's own spectrum mass.
        stream_residual = state.get("stream_residual")
        stream_fold_steps = state.get("stream_fold_steps")
        if self.solver == "streaming":
            if update_eigen:
                stream_residual = jnp.maximum(
                    1.0 - spectrum_mass, jnp.float32(0.0)
                )
                stream_fold_steps = jnp.zeros((), jnp.int32)
            elif update_factors and (
                not self.factor_comm.defer or flush_factors
            ):
                with phase("kfac_refresh", "trace/kfac/stream_fold"):
                    eigen, stacked, stream_residual = (
                        streaming_ops.fold_replicated(
                            facs, eigen, stacked, self.eps
                        )
                    )
                stream_fold_steps = state["stream_fold_steps"] + 1

        # Precondition every layer's gradient, every step
        # (kfac_preconditioner.py:401-404) — batched over same-shape layers.
        if not precond_early:
            with phase("kfac_apply", "trace/kfac/precondition"):
                new_grads, gmats, updates, nu = self._precondition_replicated(
                    grads, names, facs, eigen, stacked, lr, damping, tables,
                    bank_tape, grad_scale,
                )

        new_state = {
            "step": state["step"] + 1,
            "factors": facs,
            "eigen": eigen,
            "eigen_stacked": stacked,
        }
        if tables is not None:
            new_state["inverse_tables"] = tables
        if pending is not None:
            new_state["eigen_pending"] = pending
        if spectrum_mass is not None:
            new_state["spectrum_mass"] = spectrum_mass
        if stream_residual is not None:
            new_state["stream_residual"] = stream_residual
            new_state["stream_fold_steps"] = stream_fold_steps
        if "factor_sync_age" in state:
            new_state["factor_sync_age"] = (
                jnp.zeros((), jnp.int32)
                if flush_factors
                else state["factor_sync_age"] + int(update_factors)
            )
        if wire_error is not None:
            # unchanged between flushes; replaced by the residuals of the
            # quantized merge on flush steps
            new_state["wire_error"] = wire_error
        if "eigen_swap_slip" in state:
            # 1 while a fully-landed pending basis waits for a slipped swap
            # (set on the final-chunk step that withheld swap_eigen), 0 once
            # any swap/refresh installs a basis. Pure function of the static
            # flags, so it adds no step variants of its own.
            last_chunk_no_swap = (
                eigen_chunk is not None
                and eigen_chunk[0] == eigen_chunk[1] - 1
                and not swap_eigen
            )
            new_state["eigen_swap_slip"] = (
                jnp.zeros((), jnp.int32)
                if (swap_eigen or update_eigen)
                else state["eigen_swap_slip"] + int(last_chunk_no_swap)
            )
        if self.track_diagnostics:
            new_state["diagnostics"] = self._diagnostics(
                state["diagnostics"], fresh_spectra, gmats, updates, nu,
                damping, update_eigen or swap_eigen,
            )
        return new_grads, new_state

    def _precondition_replicated(
        self, grads, names, facs, eigen, stacked, lr, damping, tables=None,
        bank_tape=None, grad_scale=None,
    ):
        """The every-step precondition + KL clip of the replicated flow,
        factored out so the overlap plane can emit it either before the
        chunk-eigh (comm_overlap chunk-only steps) or after the refresh
        branches (everywhere else) without duplicating the dispatch."""
        lgrads = capture.layer_grads(grads, names)
        # the banks preconditioned from their rows: their gradient and update
        # stay in the kernel's [E, a, m] (no transpose either way)
        routed = {
            n: t for n, t in (bank_tape or {}).items()
            if tables is not None
            and precond_ops.bank_rows_pay(t[0].shape[0], *lgrads[n]["kernel"].shape)
        }
        gmats = {
            name: mat.astype(jnp.float32)
            for name, mat in capture.grad_mats(lgrads, frozenset(routed)).items()
        }
        # Shard-lens gmats (stacked 3-D, or block-structured 2-D) solve
        # shard-locally (shardwise.precondition) — they never enter the
        # generic same-shape batching / distributed-assignment paths, whose
        # shape grouping assumes plain [a, m] mats.
        shard_items = shardwise.shard_entries(names)
        norm_gmats = {n: g for n, g in gmats.items() if n not in shard_items}
        precision_args = (
            (self.precond_precision,) if self.precond_precision is not None else ()
        )
        inverse = self.precond_method == "inverse"
        if not norm_gmats:
            updates = {}
        elif self.distribute_precondition and self._world() > 1:
            owners = precondition_assignment(
                {name: tuple(g.shape) for name, g in norm_gmats.items()},
                self._world(),
                diag_a={n for n, f in facs.items() if "A_diag" in f},
            )
            dist_fn = (
                precond_ops.precondition_all_inv_distributed
                if inverse
                else precond_ops.precondition_all_distributed
            )
            updates = dist_fn(
                norm_gmats, eigen, damping, *precision_args, stacked=stacked,
                mesh=self.mesh, owners=owners,
                comm_dtype=self.precond_comm_dtype,
            )
        elif tables is not None:
            layout = self._inverse_layout(facs)[0]
            updates = precond_ops.precondition_all_inv_tables(
                {n: g for n, g in norm_gmats.items() if n not in routed},
                tables, layout, *precision_args,
            )
            # v is linear in g: the step's clip factor carries over
            scale = 1.0 if grad_scale is None else grad_scale
            for n, tape in routed.items():
                updates[n] = scale * precond_ops.precondition_bank_rows(
                    *tape, tables, layout[n], *precision_args
                )
            updates = {n: updates[n] for n in norm_gmats}  # the KL clip's order
        elif inverse:
            updates = precond_ops.precondition_all_inv(
                norm_gmats, eigen, *precision_args, stacked=stacked
            )
        else:
            updates = precond_ops.precondition_all(
                norm_gmats, eigen, damping, *precision_args, stacked=stacked
            )
        for n, (_, form, count) in shard_items.items():
            updates[n] = shardwise.precondition(
                form, count, gmats[n], eigen[n], damping
            )

        # Global KL trust-region rescale (kfac_preconditioner.py:311-334).
        nu = precond_ops.kl_clip_coefficient(
            updates, gmats, lr, self.hparams.kl_clip
        )
        new_grads = capture.write_back(grads, updates, nu, frozenset(routed))
        return new_grads, gmats, updates, nu

    def _update_owner(
        self,
        grads: PyTree,
        state: KFACState,
        *,
        a_contribs: Optional[Dict[str, jnp.ndarray]],
        g_factor_stats: Optional[Dict[str, jnp.ndarray]],
        lr: jnp.ndarray,
        damping: jnp.ndarray,
        update_factors: bool,
        update_eigen: bool,
        eigen_chunk: Optional[Tuple[int, int]],
        swap_eigen: bool,
        flush_factors: bool,
    ) -> Tuple[PyTree, KFACState]:
        """The ``factor_sharding="owner"`` step (DP-KFAC, arxiv 2206.15143).

        Same contract as the replicated flow in :meth:`update` (which
        validated the static-flag combinations before dispatching here),
        with the three wire/state moves swapped out:

        * factor EMA — per-replica ``(1−α)·contrib`` statistics
          reduce-SCATTER onto the owners' shard rows
          (``FactorComm.scatter_merge``; deferred mode accumulates into the
          full-size ``factor_local`` buffer and scatters ``α^m``-decayed at
          each flush, exact vs. replicated by EMA linearity);
        * eigen refresh — purely owner-local over the shard stacks
          (``owner_eigen_update`` / the ``plan_owner_chunks`` pipelined
          variant), zero collectives in the program;
        * precondition — each layer solves on its owner and ONE allgather
          replicates the preconditioned gradients
          (``ops.precondition.precondition_all_owner``), in
          ``precondition_all``'s emission order so the KL-clip summation
          reassociates identically.
        """
        names = list(state["factors"].keys())
        lgrads = capture.layer_grads(grads, names)
        gmats = {
            name: mat.astype(jnp.float32)
            for name, mat in capture.grad_mats(lgrads).items()
        }
        shapes = {
            name: (int(g.shape[0]), int(g.shape[1]))
            for name, g in gmats.items()
        }
        # the diag set travels in the state placeholders' key names, so the
        # step-time plan matches init()'s exactly
        diag_a = frozenset(
            n for n in names if "A_diag" in state["factors"][n]
        )
        plan = self._shard_plan(shapes, diag_a)
        alpha = self.factor_decay

        shard = state["factor_shard"]
        local = state.get("factor_local")
        if update_factors:
            if a_contribs is None or g_factor_stats is None:
                raise ValueError(
                    "update_factors=True requires a_contribs and g_factor_stats"
                )
            missing = [
                n for n in names if n not in a_contribs or n not in g_factor_stats
            ]
            if missing:
                raise ValueError(
                    f"no captured statistics for layers {missing}; the model "
                    "contains kernel-bearing modules that are not K-FAC "
                    "capture-aware — construct KFAC(layers=capture."
                    "discover_layers(model, ...)) so init() matches capture."
                )
            with phase("kfac_capture", "trace/kfac/factor_update"):
                if self.factor_comm.defer:
                    # local-only EMA delta since the last flush (starts from
                    # zero, NOT from the master copy — non-owners hold none)
                    local = {
                        name: {
                            "A": factor_ops.update_running_avg(
                                a_contribs[name], local[name]["A"], alpha
                            ),
                            "G": factor_ops.update_running_avg(
                                g_factor_stats[name], local[name]["G"], alpha
                            ),
                        }
                        for name in names
                    }
                else:
                    payload = {
                        name: {
                            "A": (1.0 - alpha)
                            * a_contribs[name].astype(jnp.float32),
                            "G": (1.0 - alpha)
                            * g_factor_stats[name].astype(jnp.float32),
                        }
                        for name in names
                    }
                    shard = self.factor_comm.scatter_merge(
                        payload, shard, plan, jnp.asarray(alpha, jnp.float32)
                    )
        if flush_factors:
            # α^m carry (m deferred capture steps since the last flush,
            # including this step's) + the scattered mean of the local
            # accumulators — the owner-sharded form of FactorComm.flush,
            # exact vs. the replicated merge by EMA linearity.
            m = state["factor_sync_age"] + int(update_factors)
            decay = jnp.power(
                jnp.asarray(alpha, jnp.float32), m.astype(jnp.float32)
            )
            shard = self.factor_comm.scatter_merge(local, shard, plan, decay)
            local = jax.tree_util.tree_map(jnp.zeros_like, local)

        eigen_shard = state["eigen_shard"]
        pending = state.get("eigen_pending_shard")
        spectrum_mass = state.get("spectrum_mass")
        # Overlap plane, mechanism (b) — owner form: chunk-only steps leave
        # eigen_shard untouched, so the precondition (and its allgather) can
        # be emitted ahead of the chunk work. See the replicated flow's
        # precond_early comment.
        precond_early = (
            self.comm_overlap and eigen_chunk is not None and not swap_eigen
        )
        if precond_early:
            with phase("kfac_apply", "trace/kfac/precondition"):
                new_grads = self._precondition_owner(
                    grads, gmats, eigen_shard, lr, damping, plan
                )
        if update_eigen:
            with phase("kfac_refresh", "trace/kfac/eigh"):
                eigen_shard = {
                    **owner_eigen_update(
                        shard,
                        plan,
                        self.mesh,
                        self.batch_axes,
                        self.eps,
                        rank_fn=self._rank_fn(),
                        eigen_dtype=self.eigen_dtype,
                    ),
                    **self._owner_diag_eigen(shard, plan),
                }
                if self.solver in ("rsvd", "streaming"):
                    spectrum_mass = owner_spectrum_mass(
                        shard,
                        eigen_shard,
                        plan,
                        self.mesh,
                        self.batch_axes,
                        rank_fn=self._rank_fn(),
                    )
        elif eigen_chunk is not None:
            c, k = eigen_chunk
            jobs = plan_owner_chunks(plan, k, rank_fn=self._rank_fn())[c]
            if c == 0:
                # fresh interval: zero the double buffer, mirroring the
                # replicated chunk path's from-zeros _assemble contract
                pending = jax.tree_util.tree_map(jnp.zeros_like, pending)
            with phase("kfac_refresh", "trace/kfac/eigh"):
                pending = owner_eigen_chunk_update(
                    shard,
                    pending,
                    jobs,
                    plan,
                    self.mesh,
                    self.batch_axes,
                    self.eps,
                    rank_fn=self._rank_fn(),
                    eigen_dtype=self.eigen_dtype,
                )
            if swap_eigen:
                eigen_shard = {
                    **pending, **self._owner_diag_eigen(shard, plan)
                }
                if self.solver == "rsvd":
                    spectrum_mass = owner_spectrum_mass(
                        shard,
                        eigen_shard,
                        plan,
                        self.mesh,
                        self.batch_axes,
                        rank_fn=self._rank_fn(),
                    )
        elif swap_eigen:
            # Bare-swap catch-up (bounded staleness), owner form: promote
            # the fully-landed pending shard without running any chunk.
            eigen_shard = {
                **pending, **self._owner_diag_eigen(shard, plan)
            }
            if self.solver == "rsvd":
                spectrum_mass = owner_spectrum_mass(
                    shard,
                    eigen_shard,
                    plan,
                    self.mesh,
                    self.batch_axes,
                    rank_fn=self._rank_fn(),
                )

        # Streaming curvature, owner form: fold the freshly merged shard
        # stacks through the on-owner bases (shard-local einsums + one psum
        # for the drift gauge — parallel/sharded_eigh.py::owner_stream_fold).
        # In deferred mode the fold rides flush steps only, so it always
        # reads globally-merged factors.
        stream_residual = state.get("stream_residual")
        stream_fold_steps = state.get("stream_fold_steps")
        if self.solver == "streaming":
            if update_eigen:
                stream_residual = jnp.maximum(
                    1.0 - spectrum_mass, jnp.float32(0.0)
                )
                stream_fold_steps = jnp.zeros((), jnp.int32)
            elif update_factors and (
                not self.factor_comm.defer or flush_factors
            ):
                with phase("kfac_refresh", "trace/kfac/stream_fold"):
                    eigen_shard, stream_residual = owner_stream_fold(
                        shard,
                        eigen_shard,
                        plan,
                        self.mesh,
                        self.batch_axes,
                        self.eps,
                        rank_fn=self._rank_fn(),
                    )
                stream_fold_steps = state["stream_fold_steps"] + 1

        if not precond_early:
            with phase("kfac_apply", "trace/kfac/precondition"):
                new_grads = self._precondition_owner(
                    grads, gmats, eigen_shard, lr, damping, plan
                )

        new_state = {
            "step": state["step"] + 1,
            "factors": state["factors"],
            "eigen": state["eigen"],
            "eigen_stacked": state["eigen_stacked"],
            "factor_shard": shard,
            "eigen_shard": eigen_shard,
        }
        if pending is not None:
            new_state["eigen_pending_shard"] = pending
        if spectrum_mass is not None:
            new_state["spectrum_mass"] = spectrum_mass
        if stream_residual is not None:
            new_state["stream_residual"] = stream_residual
            new_state["stream_fold_steps"] = stream_fold_steps
        if local is not None:
            # Pin the per-replica accumulators to the replicated spec: their
            # shards deliberately diverge (each device holds its own batch
            # shard's statistics), so a GSPMD layout choice that splits a
            # leaf whose dim happens to equal the batch world would silently
            # interleave rows from different replicas' accumulators — and
            # snapshot packing reads whole per-device copies.
            _rep = NamedSharding(self.mesh, P())
            new_state["factor_local"] = jax.tree_util.tree_map(
                lambda v: jax.lax.with_sharding_constraint(v, _rep), local
            )
            new_state["factor_sync_age"] = (
                jnp.zeros((), jnp.int32)
                if flush_factors
                else state["factor_sync_age"] + int(update_factors)
            )
        if "eigen_swap_slip" in state:
            last_chunk_no_swap = (
                eigen_chunk is not None
                and eigen_chunk[0] == eigen_chunk[1] - 1
                and not swap_eigen
            )
            new_state["eigen_swap_slip"] = (
                jnp.zeros((), jnp.int32)
                if (swap_eigen or update_eigen)
                else state["eigen_swap_slip"] + int(last_chunk_no_swap)
            )
        return new_grads, new_state

    def _precondition_owner(self, grads, gmats, eigen_shard, lr, damping, plan):
        """Owner-mode every-step precondition + KL clip, factored out so the
        overlap plane can emit it before the chunk work on chunk-only
        steps (see :meth:`_precondition_replicated`)."""
        precision_args = (
            (self.precond_precision,)
            if self.precond_precision is not None
            else ()
        )
        updates = precond_ops.precondition_all_owner(
            gmats,
            eigen_shard,
            damping,
            *precision_args,
            mesh=self.mesh,
            plan=plan,
            rank_fn=self._rank_fn(),
            eigen_dtype=self.eigen_dtype,
            axis_name=self.batch_axes,
        )
        nu = precond_ops.kl_clip_coefficient(
            updates, gmats, lr, self.hparams.kl_clip
        )
        return capture.write_back(grads, updates, nu)

    def _diagnostics(
        self,
        prev: Dict[str, Any],
        fresh_spectra: Optional[Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]],
        gmats: Dict[str, jnp.ndarray],
        updates: Dict[str, jnp.ndarray],
        nu: jnp.ndarray,
        damping,
        update_eigen: bool,
    ) -> Dict[str, Any]:
        """Build the next diagnostics pytree (same structure as init()'s).

        Spectrum-derived entries (min/max damped eig, per-layer factor
        condition numbers) refresh only when ``fresh_spectra`` is present —
        an eigen-method eigen-update step — and carry forward otherwise
        (the inverse method never materializes eigenvalues). The norm/
        cosine/staleness entries are cheap reductions computed every step.
        """
        lam = jnp.asarray(damping, jnp.float32)
        min_eig = prev["min_damped_eig"]
        max_eig = prev["max_damped_eig"]
        layer_cond = prev["layer_cond"]
        if fresh_spectra is not None:
            mins, maxs, layer_cond = [], [], {}
            for n, (da, dg) in fresh_spectra.items():
                da = da.astype(jnp.float32)
                dg = dg.astype(jnp.float32)
                da_mn, da_mx = jnp.min(da), jnp.max(da)
                dg_mn, dg_mx = jnp.min(dg), jnp.max(dg)
                # λ of G ⊗ A are products of factor eigenvalues (dA/dG are
                # already floored ≥ 0 by the eigh path's eps floor)
                mins.append(dg_mn * da_mn)
                maxs.append(dg_mx * da_mx)
                # damped condition number: λ added to both ends bounds the
                # ratio exactly as the damped solve does — a raw min of 0
                # (floored eigenvalue) reads as (max+λ)/λ, the true
                # amplification spread of the damped inverse, not inf
                layer_cond[n] = {
                    "cond_A": (da_mx + lam) / (da_mn + lam),
                    "cond_G": (dg_mx + lam) / (dg_mn + lam),
                }
            min_eig = jnp.min(jnp.stack(mins)) + lam
            max_eig = jnp.max(jnp.stack(maxs)) + lam

        # Update-vs-gradient geometry, every step: the preconditioned
        # direction's norm (as applied: ν-scaled) and its cosine to the raw
        # gradient. cos → 0 or negative flags a curvature estimate at war
        # with the loss signal; ‖update‖ spiking with ν ≈ 1 flags a trust
        # region that is not engaging.
        sq_g = sq_v = dot = jnp.asarray(0.0, jnp.float32)
        for name, v in updates.items():
            g = gmats[name].astype(jnp.float32)
            v = v.astype(jnp.float32)
            sq_g = sq_g + jnp.sum(g * g)
            sq_v = sq_v + jnp.sum(v * v)
            dot = dot + jnp.sum(v * g)
        grad_norm = jnp.sqrt(sq_g)
        upd_norm = jnp.sqrt(sq_v)
        cos = dot / jnp.maximum(grad_norm * upd_norm, 1e-30)

        return {
            "nu": nu,
            "min_damped_eig": min_eig,
            "max_damped_eig": max_eig,
            "grad_norm": grad_norm,
            "update_norm": nu * upd_norm,
            "update_grad_cos": cos,
            # steps since the eigenbasis (or inverse) was last recomputed —
            # static flag, so this is a plain int32 counter in-graph
            "eigen_stale_steps": (
                jnp.zeros((), jnp.int32)
                if update_eigen
                else prev["eigen_stale_steps"] + 1
            ),
            "layer_cond": layer_cond,
        }
