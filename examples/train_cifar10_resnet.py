"""CIFAR-10 ResNet training with distributed K-FAC on TPU (JAX).

Flag-parity port of the reference CLI (examples/pytorch_cifar10_resnet.py:
30-94): same hyperparameter surface and defaults, same K-FAC gating rule
(``--kfac-update-freq 0`` → plain SGD). Data-parallelism is a
``jax.sharding.Mesh`` over all local devices instead of Horovod ranks, and
the whole train step (fwd+bwd+grad mean+K-FAC+SGD) is one compiled program.

Run (single host, all chips):
    python examples/train_cifar10_resnet.py --model resnet32 --epochs 100 \
        --kfac-update-freq 10 --data-dir /path/to/cifar
Synthetic smoke:
    python examples/train_cifar10_resnet.py --synthetic --epochs 1 \
        --steps-per-epoch 30
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import _env  # noqa: F401  (platform forcing — must precede jax use)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu import (
    KFAC,
    EigenRefreshCadence,
    KFACParamScheduler,
    observability,
    runtime,
)
from kfac_pytorch_tpu.compile_cache import (
    RecompileMonitor,
    expected_step_variants,
)
from kfac_pytorch_tpu.models import cifar_resnet
from kfac_pytorch_tpu.parallel import launch
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh, put_global_batch
from kfac_pytorch_tpu.training import (
    TrainState,
    create_lr_schedule,
    make_masked_eval_step,
    make_train_step,
)
from kfac_pytorch_tpu.training import checkpoint as ckpt
from kfac_pytorch_tpu.training import data as data_lib
from kfac_pytorch_tpu.training import profiling
from kfac_pytorch_tpu.training.metrics import Metric, ScalarWriter
from kfac_pytorch_tpu.training.step import make_sgd

# per-step K-FAC health keys (beyond the original nu / min-eig pair) that
# --kfac-diagnostics reduces to per-epoch means; names match
# observability.diagnostics.diagnostic_metrics output
DIAG_EXTRA_KEYS = (
    "kfac_max_damped_eig",
    "kfac_cond_max",
    "kfac_grad_norm",
    "kfac_update_norm",
    "kfac_update_grad_cos",
    "kfac_eigen_stale_steps",
)


def parse_args(argv=None):
    # Flag surface mirrors pytorch_cifar10_resnet.py:30-94.
    p = argparse.ArgumentParser(
        description="CIFAR-10 K-FAC Example (TPU/JAX)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--data-dir", default=None, help="CIFAR-10 data dir")
    p.add_argument("--synthetic", action="store_true", help="use synthetic data")
    # knobs for the learnable stand-in (used when no CIFAR-10 is on disk):
    # the published convergence twins pin these so the task has a real
    # accuracy ceiling and post-decay epochs stay discriminative
    p.add_argument("--synth-classes", type=int, default=10,
                   help="stand-in class count (also sizes the model head)")
    p.add_argument("--synth-prototypes", type=int, default=10,
                   help="stand-in prototypes per class")
    p.add_argument("--synth-noise", type=float, default=0.55,
                   help="stand-in additive pixel noise sigma")
    p.add_argument("--synth-label-noise", type=float, default=0.08,
                   help="stand-in TRAIN label flip fraction")
    p.add_argument("--synth-val-label-noise", type=float, default=0.0,
                   help="stand-in VAL label flip fraction f (flips always "
                        "land wrong: hard accuracy ceiling of exactly 1-f)")
    p.add_argument("--log-dir", default="./logs", help="TensorBoard/JSONL log dir")
    p.add_argument("--checkpoint-dir", default=None, help="checkpoint dir (enables save/resume)")
    p.add_argument("--preempt-save-dir", default=None,
                   help="elastic snapshot dir: SIGTERM takes an emergency "
                        "snapshot and a restart scan-resumes the newest one "
                        "(docs/ELASTIC.md)")
    p.add_argument("--snapshot-every", type=int, default=0,
                   help="elastic: also snapshot every N steps "
                        "(needs --preempt-save-dir; 0 = emergency-only)")
    p.add_argument("--model", default="resnet32", help="cifar resnet variant")
    p.add_argument("--batch-size", type=int, default=128, help="per-device train batch size")
    p.add_argument("--batches-per-allreduce", type=int, default=1,
                   help="gradient-accumulation microbatches per optimizer step "
                        "(pytorch_cifar10_resnet.py:48-52)")
    p.add_argument("--stats-all-microbatches", action="store_true",
                   help="capture K-FAC statistics on every accumulation "
                        "microbatch and average them (equals full-batch "
                        "stats) instead of the reference's last-microbatch "
                        "behavior")
    p.add_argument("--num-workers", type=int, default=4,
                   help="native loader threads (0 = single-threaded numpy "
                        "pipeline; pytorch_cifar10_resnet.py:118)")
    p.add_argument("--val-batch-size", type=int, default=128, help="per-device val batch size")
    p.add_argument("--epochs", type=int, default=100)
    p.add_argument("--steps-per-epoch", type=int, default=None, help="cap steps (synthetic/smoke)")
    p.add_argument("--base-lr", type=float, default=0.1, help="per-device lr (scaled by world)")
    p.add_argument("--lr-decay", nargs="+", type=int, default=[35, 75, 90])
    p.add_argument("--warmup-epochs", type=float, default=5)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=5e-4)
    p.add_argument("--label-smoothing", type=float, default=0.0)
    # KFAC hyperparameters (defaults: pytorch_cifar10_resnet.py:56-78)
    p.add_argument("--kfac-update-freq", type=int, default=10, help="0 disables K-FAC")
    p.add_argument("--kfac-cov-update-freq", type=int, default=1)
    p.add_argument("--stat-decay", type=float, default=0.95)
    p.add_argument("--damping", type=float, default=0.003)
    p.add_argument("--damping-alpha", type=float, default=0.5)
    p.add_argument("--damping-schedule", nargs="+", type=int, default=[40, 80])
    p.add_argument("--kl-clip", type=float, default=0.001)
    p.add_argument("--diag-blocks", type=int, default=1)
    p.add_argument("--diag-warmup", type=int, default=0)
    p.add_argument("--distribute-precondition", action="store_true",
                   help="shard the every-step eigenbasis rotations across "
                        "the mesh (one owner device per layer + psum "
                        "exchange); recommended at pod scale, see "
                        "docs/PERF.md")
    p.add_argument("--distribute-layer-factors", type=lambda s: s.lower() == "true",
                   default=None, nargs="?")
    p.add_argument("--kfac-update-freq-alpha", type=float, default=10)
    p.add_argument("--kfac-update-freq-schedule", nargs="+", type=int, default=None)
    p.add_argument("--init-from-torch", default=None,
                   help="initialize model weights from a reference CIFAR "
                        "ResNet checkpoint (.pth/.pth.tar); optimizer and "
                        "K-FAC state start fresh")
    p.add_argument("--precond-comm-dtype", default=None,
                   choices=[None, "bf16"],
                   help="downcast the distributed-precondition psum payload "
                        "(the reference's --fp16-allreduce compression, "
                        "applied to the preconditioned-grad exchange)")
    p.add_argument("--grad-comm-dtype", default=None, choices=[None, "bf16"],
                   help="downcast the per-step data-parallel gradient mean "
                        "on the wire (the reference's --fp16-allreduce on "
                        "DistributedOptimizer, pytorch_cifar10_resnet.py:"
                        "190-195); None = exact f32 reduction")
    p.add_argument("--factor-comm-dtype", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="wire dtype of the bucketed K-FAC factor-statistics "
                        "exchange (parallel/comm.py); f32 = bitwise parity "
                        "with the per-layer exchange; int8 = block-scaled "
                        "codes + error feedback at 0.51x the bf16 bytes "
                        "(requires --factor-comm-freq > 1; docs/PERF.md "
                        "'Sub-bf16 wire')")
    p.add_argument("--factor-comm-freq", type=int, default=1,
                   help="allreduce factor statistics every N capture steps "
                        "instead of every one (merged running averages, "
                        "always flushed before an eigen refresh); 1 = "
                        "per-step exchange, exact")
    p.add_argument("--factor-sharding", default="replicated",
                   choices=["replicated", "owner"],
                   help="owner: DP-KFAC owner-sharded curvature — factor "
                        "stats reduce-scatter onto each layer's eigen-owner, "
                        "eigen bases live only there, and ONE allgather "
                        "replicates the preconditioned grads; factor+eigen "
                        "memory and wire scale O(model/devices) "
                        "(docs/PERF.md); replicated = exact prior behavior")
    p.add_argument("--precond-method", default="eigen",
                   choices=["eigen", "inverse"],
                   help="eigen: reference-parity eigenbasis solve (damping "
                        "fresh every step); inverse: pi-corrected factored "
                        "Tikhonov damping + Cholesky inverses (2 matmuls/"
                        "layer per step instead of 4; docs/PERF.md)")
    p.add_argument("--precond-precision", default=None,
                   choices=["default", "high", "highest"],
                   help="matmul precision of the every-step eigenbasis "
                        "rotations (docs/PERF.md); None = library default")
    p.add_argument("--eigen-dtype", default="f32", choices=["f32", "bf16"],
                   help="storage dtype of the eigenvector matrices (bf16 "
                        "halves the dominant precondition HBM stream)")
    p.add_argument("--eigh-chunks", type=int, default=1,
                   help="pipeline the eigen refresh over this many steps "
                        "after each --kfac-update-freq boundary (double-"
                        "buffered basis, swapped when all chunks land); 1 = "
                        "monolithic refresh, bit-exact with prior releases "
                        "(docs/PERF.md)")
    p.add_argument("--factor-kernel", default="auto",
                   choices=["auto", "pallas", "dense"],
                   help="conv A-factor statistics kernel: pallas = fused "
                        "patch-covariance Pallas kernel (no im2col patch "
                        "tensor, enables large batches; docs/PERF.md), dense "
                        "= im2col oracle, auto = dense (the Pallas kernel is "
                        "opt-in: the v5e compiler refuses it at ResNet-50 "
                        "shapes, docs/PERF.md)")
    p.add_argument("--bf16", action="store_true",
                   help="bfloat16 conv/matmul compute (params + K-FAC factor "
                        "math stay f32)")
    p.add_argument("--profile-epoch", type=int, default=None,
                   help="capture a jax.profiler trace of this epoch into --log-dir")
    p.add_argument("--telemetry-dir", default=None,
                   help="enable structured telemetry and write metrics.prom "
                        "(Prometheus textfile) + telemetry.jsonl there each "
                        "epoch: per-phase span timings, recompile counter, "
                        "K-FAC health gauges (docs/OBSERVABILITY.md)")
    p.add_argument("--kfac-diagnostics", action="store_true",
                   help="log per-epoch K-FAC stability telemetry (KL-clip "
                        "coefficient nu min/mean, min damped eigenvalue) to "
                        "--log-dir")
    p.add_argument("--solver", default="eigh",
                   choices=["eigh", "rsvd", "streaming"],
                   help="curvature eigensolver: eigh = full (dense) "
                        "eigendecomposition, rsvd = randomized truncated "
                        "eigensolve + low-rank Woodbury apply for factor "
                        "sides >= --solver-auto-threshold, streaming = rsvd "
                        "layout with per-step matmul-only folds and "
                        "drift-gated re-orthonormalization (docs/PERF.md)")
    p.add_argument("--solver-rank", type=int, default=128,
                   help="eigenpairs kept per truncated factor side "
                        "(--solver rsvd); watch kfac/spectrum_mass_captured "
                        "to size it")
    p.add_argument("--solver-auto-threshold", type=int, default=512,
                   help="factor sides at least this large use the truncated "
                        "solver; smaller sides stay dense (--solver rsvd)")
    p.add_argument("--stream-drift-threshold", type=float, default=0.05,
                   help="--solver streaming: re-orthonormalize at a refresh "
                        "boundary only when the residual-mass drift gauge "
                        "(kfac/stream_residual_mass) exceeds this; 0 = "
                        "re-orth every boundary, exactly periodic rsvd")
    p.add_argument("--comm-overlap", action="store_true",
                   help="fuse the factor-statistics reduction into the "
                        "gradient stream: the bucketed factor psums issue "
                        "before the gradient pmean so the collectives "
                        "interleave with backprop instead of queuing after "
                        "it (multi-device mesh only; bitwise-identical "
                        "numerics; docs/PERF.md)")
    p.add_argument("--staleness-budget", type=int, default=0,
                   help="let a deferred factor flush or a completed pending "
                        "eigen swap slip up to this many steps under "
                        "measured comm/compute pressure (needs "
                        "--factor-comm-freq > 1, --eigh-chunks > 1 or "
                        "--service-devices > 0; 0 = never slip; watch the "
                        "kfac/staleness_* gauges)")
    p.add_argument("--service-devices", type=int, default=0,
                   help="carve this many devices out of the mesh as "
                        "dedicated curvature workers (kfac_pytorch_tpu/"
                        "service/): the eigen refresh leaves the training "
                        "step entirely — factor snapshots publish at each "
                        "--kfac-update-freq boundary, refreshed bases "
                        "install between steps, --staleness-budget bounds "
                        "the install slip (docs/SERVICE.md); 0 = inline "
                        "refresh")
    p.add_argument("--profile", default=None,
                   choices=["safe", "memory", "production"],
                   help="resolve the K-FAC perf levers from a named planner "
                        "profile (planner/cost_model.py) using this model's "
                        "factor shapes and the mesh; explicit lever flags "
                        "win over the profile's choices (docs/PLANNER.md)")
    p.add_argument("--autotune-steps", type=int, default=0,
                   help="time the resolved plan against its conservative "
                        "fallbacks for this many warmup steps each and pin "
                        "the winner (0 = trust the cost model; needs "
                        "--profile; docs/PLANNER.md)")
    p.add_argument("--bn-recal-batches", type=int, default=0,
                   help="refresh BatchNorm running statistics with this many "
                        "clean train-mode forwards before each eval (0 = "
                        "reference parity). Removes the transient val-accuracy "
                        "dips caused by stale BN EMAs at high lr "
                        "(training/step.py::make_bn_recal_step)")
    p.add_argument("--seed", type=int, default=42)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    rng = np.random.RandomState(args.seed)

    # enable BEFORE any spans fire (launch.initialize below has comm spans);
    # with the overlap plane on, span barriers are dropped — a
    # block_until_ready between dispatches would serialize the very
    # collectives the overlap interleaves
    tel = observability.configure(
        enabled=bool(args.telemetry_dir),
        block_spans=False if args.comm_overlap else None,
    )

    launch.initialize()  # multi-host wiring; no-op single-process
    if args.service_devices > 0:
        from kfac_pytorch_tpu.parallel.mesh import split_service_mesh

        mesh, service_workers = split_service_mesh(args.service_devices)
    else:
        mesh, service_workers = data_parallel_mesh(), ()
    world = mesh.devices.size
    n_proc = launch.size()
    accum = args.batches_per_allreduce
    global_bs = args.batch_size * world
    local_bs = global_bs // n_proc
    if launch.is_primary():
        print(
            f"devices={world} hosts={n_proc} global_batch={global_bs}"
            + (f" x{accum} accum" if accum > 1 else "")
        )

    model = cifar_resnet.get_model(
        args.model, dtype=jnp.bfloat16 if args.bf16 else None,
        num_classes=args.synth_classes,
    )
    init_images = jnp.zeros((global_bs, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(args.seed), init_images, train=True)
    params, batch_stats = variables["params"], variables.get("batch_stats", {})
    if args.init_from_torch:
        # migrate a reference/torchvision checkpoint; validation of
        # paths/shapes/dtypes lives with the converter
        # (torch_interop.init_params_from_checkpoint)
        from kfac_pytorch_tpu import torch_interop

        params, batch_stats = torch_interop.init_params_from_checkpoint(
            args.init_from_torch, args.model, params, batch_stats
        )
        if launch.is_primary():
            print(f"initialized weights from torch checkpoint "
                  f"{args.init_from_torch}")

    use_kfac = args.kfac_update_freq > 0
    lr_base = args.base_lr * world
    tx = make_sgd(momentum=args.momentum, weight_decay=args.wd)

    kfac = None
    kfac_sched = None
    if use_kfac:
        from kfac_pytorch_tpu import capture as capture_lib

        kfac_layers = capture_lib.discover_layers(model, init_images, train=True)
        profile_shapes = None
        if args.profile:
            from kfac_pytorch_tpu import planner

            # factor shapes for the cost model, from the live params
            profile_shapes = planner.model_facts(params, layers=kfac_layers)

        def build_kfac(profile=args.profile):
            return KFAC(
                layers=kfac_layers,
                lr=lr_base,
                factor_decay=args.stat_decay,
                damping=args.damping,
                kl_clip=args.kl_clip,
                fac_update_freq=args.kfac_cov_update_freq,
                kfac_update_freq=args.kfac_update_freq,
                diag_blocks=args.diag_blocks,
                diag_warmup=args.diag_warmup,
                distribute_layer_factors=args.distribute_layer_factors,
                distribute_precondition=args.distribute_precondition,
                mesh=mesh if world > 1 else None,
                precond_precision=args.precond_precision,
                precond_method=args.precond_method,
                precond_comm_dtype=(jnp.bfloat16
                                    if args.precond_comm_dtype == "bf16" else None),
                eigen_dtype=jnp.bfloat16 if args.eigen_dtype == "bf16" else jnp.float32,
                track_diagnostics=args.kfac_diagnostics,
                eigh_chunks=args.eigh_chunks,
                factor_kernel=args.factor_kernel,
                factor_comm_dtype=args.factor_comm_dtype,
                factor_comm_freq=args.factor_comm_freq,
                solver=args.solver,
                solver_rank=args.solver_rank,
                solver_auto_threshold=args.solver_auto_threshold,
                stream_drift_threshold=args.stream_drift_threshold,
                factor_sharding=args.factor_sharding,
                comm_overlap=args.comm_overlap,
                staleness_budget=args.staleness_budget,
                service_devices=args.service_devices,
                profile=profile,
                profile_shapes=profile_shapes,
            )

        kfac = build_kfac()
        if kfac.plan is not None and launch.is_primary():
            drop = (
                f" (dropped: {', '.join(kfac.plan_dropped)})"
                if kfac.plan_dropped else ""
            )
            print(kfac.plan.describe() + drop)
        if args.autotune_steps and kfac.plan is not None:
            from _autotune import autotune_kfac

            def _fresh_state(k):
                # the train step donates its state (training/step.py), and
                # device_put to an already-matching sharding aliases — copy
                # so a timed candidate can't free the master params
                copy = lambda t: jax.tree_util.tree_map(
                    lambda x: jnp.array(x, copy=True), t
                )
                p = copy(params)
                s = TrainState(
                    step=jnp.zeros((), jnp.int32), params=p,
                    batch_stats=copy(batch_stats), opt_state=tx.init(p),
                    kfac_state=k.init(p),
                )
                if k.owner_sharded:
                    kstate = s.kfac_state
                    s = s.replace(kfac_state=None)
                    s = jax.device_put(s, NamedSharding(mesh, P()))
                    return s.replace(kfac_state=kstate)
                return jax.device_put(s, NamedSharding(mesh, P()))

            def _build_step(k):
                return make_train_step(
                    model, tx, k, label_smoothing=args.label_smoothing,
                    train_kwargs={"train": True}, accum_steps=accum,
                    stats_all_microbatches=args.stats_all_microbatches,
                    mesh=mesh if args.grad_comm_dtype else None,
                    grad_comm_dtype=(jnp.bfloat16
                                     if args.grad_comm_dtype == "bf16" else None),
                )

            warm = put_global_batch(
                mesh,
                (rng.randn(local_bs * accum, 32, 32, 3).astype(np.float32),
                 rng.randint(0, args.synth_classes, size=local_bs * accum)
                 .astype(np.int32)),
                accum_steps=accum,
            )
            kfac, _ = autotune_kfac(
                kfac, build_kfac, _fresh_state, _build_step, warm,
                jnp.float32(lr_base), args.autotune_steps,
                broadcast=launch.broadcast_host_value,
                log=print if launch.is_primary() else None,
            )
        kfac_sched = KFACParamScheduler(
            kfac,
            damping_alpha=args.damping_alpha,
            damping_schedule=args.damping_schedule,
            update_freq_alpha=args.kfac_update_freq_alpha,
            update_freq_schedule=args.kfac_update_freq_schedule,
        )

    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        kfac_state=kfac.init(params) if kfac else None,
    )

    resume_from_epoch = 0
    if args.checkpoint_dir:
        state, resume_from_epoch = ckpt.auto_resume(args.checkpoint_dir, state)
        # hosts must agree (checkpoints may live on host-local disk and only
        # the primary writes them; the reference broadcasts the epoch too,
        # pytorch_imagenet_resnet.py:136-140)
        resume_from_epoch = int(launch.broadcast_host_value(resume_from_epoch))
        # checked only AFTER the broadcast: raising on a subset of hosts
        # would leave the others hanging in the collective
        if resume_from_epoch and args.init_from_torch:
            raise SystemExit(
                f"--init-from-torch was given but {args.checkpoint_dir} "
                f"holds an epoch-{resume_from_epoch - 1} checkpoint that "
                "auto-resume just restored over the migrated weights; use a "
                "fresh --checkpoint-dir or drop --init-from-torch"
            )
        if resume_from_epoch and kfac_sched:
            kfac_sched.epoch = resume_from_epoch
        if resume_from_epoch and launch.is_primary():
            print(f"resumed from epoch {resume_from_epoch - 1}")

    # replicate state over the mesh; batches are sharded on the data axis.
    # Owner-sharded curvature is placed per its own contract instead —
    # factor/eigen shards land on their owners (a freshly restored
    # checkpoint is re-homed the same way, ckpt.rehome_kfac_state)
    if kfac is not None and kfac.owner_sharded:
        kstate = ckpt.rehome_kfac_state(kfac, state.kfac_state)
        state = state.replace(kfac_state=None)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        state = state.replace(kfac_state=kstate)
    else:
        state = jax.device_put(state, NamedSharding(mesh, P()))

    train_step = make_train_step(
        model, tx, kfac, label_smoothing=args.label_smoothing,
        train_kwargs={"train": True}, accum_steps=accum,
        stats_all_microbatches=args.stats_all_microbatches,
        mesh=mesh if args.grad_comm_dtype else None,
        grad_comm_dtype=jnp.bfloat16 if args.grad_comm_dtype == "bf16" else None,
    )
    eval_step = make_masked_eval_step(
        model, label_smoothing=args.label_smoothing, eval_kwargs={"train": False}
    )
    bn_recal = None
    if args.bn_recal_batches:
        from kfac_pytorch_tpu.training.step import make_bn_recal_step

        # built once: a per-epoch make_* call would be a fresh jit wrapper
        # (and a recompile) every epoch
        bn_recal = make_bn_recal_step(model, {"train": True})
    lr_factor = create_lr_schedule(world, args.warmup_epochs, args.lr_decay)

    cifar_dir = None if args.synthetic else data_lib.find_cifar10(args.data_dir)
    # host-agreement collectives — EVERY host must reach these, in this
    # order, regardless of its local state: (1) only train on real data when
    # every host found it (a partial mount must not desync the pod), (2) only
    # use the native pipeline when every host can build/load it (its shuffle
    # RNG differs from numpy's, so a split choice breaks disjoint sharding).
    all_have_data = bool(launch.host_min(cifar_dir is not None))
    # both decisions are host-agreed collectives, reached by every host in
    # the same order regardless of local state; the will_have_arrays gate
    # (host-consistent: args are identical everywhere) skips the slow
    # native-lib g++ build on pure --synthetic runs that never use it
    will_have_arrays = all_have_data or not args.synthetic
    use_native = bool(
        launch.host_min(
            will_have_arrays and args.num_workers > 0 and runtime.native_available()
        )
    )
    if cifar_dir and not all_have_data:
        print(f"host {launch.rank()}: data found but other hosts lack it; using stand-in data")
        cifar_dir = None
    # checked only AFTER the host-agreed fallback above: cifar_dir is now
    # identical on every host, so this SystemExit fires uniformly instead of
    # desyncing a pod where only some hosts have the data on disk
    synth_overrides = [
        flag
        for flag, value, default in (
            ("--synth-classes", args.synth_classes, 10),
            ("--synth-prototypes", args.synth_prototypes, 10),
            ("--synth-noise", args.synth_noise, 0.55),
            ("--synth-label-noise", args.synth_label_noise, 0.08),
            ("--synth-val-label-noise", args.synth_val_label_noise, 0.0),
        )
        if value != default
    ]
    if cifar_dir and synth_overrides:
        raise SystemExit(
            f"{'/'.join(synth_overrides)} only apply to the learnable "
            "stand-in, but real CIFAR-10 (10 classes) was found on disk — "
            "the flags would be silently ignored; drop them or the data"
        )
    train_loader = None
    x_train = x_val = None
    if cifar_dir:
        x_train, y_train = data_lib.load_cifar10(cifar_dir, train=True)
        x_val, y_val = data_lib.load_cifar10(cifar_dir, train=False)
        source = f"CIFAR-10 from {cifar_dir}"
    elif not args.synthetic:
        # zero-egress image, no dataset on disk: use the deterministic
        # LEARNABLE stand-in so convergence comparisons (K-FAC vs SGD per
        # epoch) remain meaningful; --synthetic keeps the pure-noise
        # benchmark pipeline
        (x_train, y_train), (x_val, y_val) = data_lib.synthetic_cifar_like(
            num_classes=args.synth_classes,
            prototypes_per_class=args.synth_prototypes,
            noise=args.synth_noise,
            label_noise=args.synth_label_noise,
            val_label_noise=args.synth_val_label_noise,
            seed=args.seed,
        )
        source = "synthetic-learnable stand-in (no CIFAR-10 on this image)"
    if x_train is not None:
        steps_per_epoch = len(x_train) // (global_bs * accum)
        if use_native:
            train_loader = runtime.NativeEpochLoader(
                x_train, y_train, local_bs * accum, shuffle=True, augment=True,
                num_shards=n_proc, shard_index=launch.rank(),
                num_workers=args.num_workers,
            )
        if launch.is_primary():
            pipe = "native" if train_loader else "numpy"
            print(f"{source}: {len(x_train)} train / {len(x_val)} val ({pipe} pipeline)")
    else:
        steps_per_epoch = args.steps_per_epoch or 50
    if args.steps_per_epoch:
        steps_per_epoch = min(steps_per_epoch, args.steps_per_epoch)

    writer = ScalarWriter(args.log_dir, enabled=jax.process_index() == 0)
    tel_writer = ScalarWriter(
        args.telemetry_dir,
        enabled=tel.enabled and launch.is_primary(),
        filename="telemetry.jsonl",
    )
    recompiles = RecompileMonitor(tel)
    # legitimate variant counts: plain/factors/factors+eigen — or the
    # chunked-refresh set under --eigh-chunks — ×2 while a diag_warmup
    # schedule is active (compile_cache.expected_step_variants)
    recompiles.watch("train_step", train_step, expected_step_variants(kfac))
    recompiles.watch("eval_step", eval_step, 1)
    if bn_recal is not None:
        recompiles.watch("bn_recal", bn_recal, 1)
    step = int(jax.device_get(state.step))
    # host-side refresh cadence: identical to kfac_flags_for_step at
    # --eigh-chunks 1, chunk/swap flags beyond (scheduler.EigenRefreshCadence)
    cadence = EigenRefreshCadence(kfac)
    if kfac is not None and getattr(kfac, "solver", "eigh") == "streaming":
        # drift signal for the cadence's boundary decisions: one scalar
        # device_get per kfac_update_freq boundary (not per step), read off
        # the LIVE state — the lambda closes over the rebinding variable
        kfac.stream_drift_signal = lambda: float(
            jax.device_get(state.kfac_state["stream_residual"]))

    sup = None
    resume_skip = 0
    if args.preempt_save_dir:
        from kfac_pytorch_tpu import elastic

        sup = elastic.Supervisor(
            args.preempt_save_dir, snapshot_every=args.snapshot_every,
            kfac=kfac, cadence=cadence,
            heartbeat_every=max(1, args.snapshot_every or steps_per_epoch),
            fault_injector=elastic.maybe_injector(),
        )
        sup.install_signal_handlers()
        hit = sup.scan_resume(jax.device_get(state), params=state.params)
        if hit is not None:
            state, _manifest, step = hit
            # re-place exactly like a cold start: owner-sharded kfac_state
            # keeps the placement scan_resume gave it, everything else
            # (including replicated-mode kfac_state, which rehome passes
            # through as host arrays) is replicated over the mesh
            if kfac is not None and kfac.owner_sharded:
                kstate = state.kfac_state
                state = jax.device_put(
                    state.replace(kfac_state=None), NamedSharding(mesh, P())
                )
                state = state.replace(kfac_state=kstate)
            else:
                state = jax.device_put(state, NamedSharding(mesh, P()))
            resume_from_epoch = step // steps_per_epoch
            resume_skip = step % steps_per_epoch
            if kfac_sched:
                kfac_sched.epoch = resume_from_epoch
            if launch.is_primary():
                print(f"elastic: resumed from snapshot at step {step}")
    preempted = False

    svc = None
    if kfac is not None and args.service_devices > 0:
        from kfac_pytorch_tpu.service import CurvatureService

        svc = CurvatureService(
            kfac, cadence, worker_devices=service_workers, supervisor=sup,
        )
        if launch.is_primary():
            print(
                f"curvature service: {len(service_workers)} worker "
                f"device(s), staleness budget {svc.staleness_budget}"
            )

    for epoch in range(resume_from_epoch, args.epochs):
        if kfac_sched:
            kfac_sched.step(epoch=epoch)
        if train_loader is not None:
            batches = train_loader.epoch(args.seed + epoch)
        elif x_train is not None:
            batches = data_lib.epoch_batches(
                x_train, y_train, local_bs * accum, shuffle=True, augment=True,
                seed=args.seed + epoch,
                num_shards=n_proc, shard_index=launch.rank(),
            )
        else:
            batches = data_lib.synthetic_batches(
                local_bs * accum, (32, 32, 3), args.synth_classes,
                steps_per_epoch, seed=args.seed
            )
        t0 = time.perf_counter()
        loss_m, acc_m = Metric("train/loss"), Metric("train/accuracy")
        nu_min, nu_sum, nu_n, eig_min = 1.0, 0.0, 0, None
        diag_acc = {}  # extra diagnostic keys -> (sum, count)

        def eat(m):
            nonlocal nu_min, nu_sum, nu_n, eig_min
            loss_m.update(m["loss"])
            acc_m.update(m["accuracy"])
            if "kfac_nu" in m:
                nu = float(m["kfac_nu"])
                nu_min, nu_sum, nu_n = min(nu_min, nu), nu_sum + nu, nu_n + 1
                e = float(m["kfac_min_damped_eig"])
                eig_min = e if eig_min is None else min(eig_min, e)
            if "kfac_spectrum_mass" in m:
                tel.set_gauge(
                    "kfac/spectrum_mass_captured",
                    float(m["kfac_spectrum_mass"]),
                )
            for k in DIAG_EXTRA_KEYS:
                if k in m:
                    s, c = diag_acc.get(k, (0.0, 0))
                    diag_acc[k] = (s + float(m[k]), c + 1)

        # metrics fetched a few steps late: the loop stays async (no
        # per-step host sync) while the lag window bounds in-flight
        # batches/steps so queued input buffers can't accumulate in HBM.
        # With --telemetry-dir the step-variant spans block() on the step's
        # metrics instead — a deliberate per-step sync that buys honest
        # device-inclusive per-variant timings.
        pending = []
        with profiling.maybe_trace(args.log_dir, args.profile_epoch == epoch):
            for i, (xb, yb) in enumerate(batches):
                if i >= steps_per_epoch:
                    break
                if epoch == resume_from_epoch and i < resume_skip:
                    continue  # mid-epoch snapshot resume: keep i == step phase
                lr = lr_base * lr_factor(epoch + i / steps_per_epoch)
                damping = kfac.hparams.damping if kfac else 0.0
                flags = cadence.flags_for_step(step, epoch)
                if svc is not None:
                    # install the newest complete basis before the step
                    # (blocks only at the staleness deadline)
                    state = state.replace(
                        kfac_state=svc.before_step(step, state.kfac_state)
                    )
                with tel.span("comm/host_to_device"):
                    batch = put_global_batch(mesh, (xb, yb), accum_steps=accum)
                if flags.get("eigen_chunk") is not None:
                    sp = tel.span("step/eigen_chunk")
                elif not flags.get("update_factors"):
                    sp = tel.span("step/plain")
                elif flags.get("update_eigen"):
                    sp = tel.span("step/eigen")
                else:
                    sp = tel.span("step/factors")
                with sp:
                    state, metrics = train_step(
                        state, batch, jnp.float32(lr), jnp.float32(damping),
                        **flags
                    )
                    sp.block(metrics)
                if svc is not None:
                    # boundary steps publish the just-folded factor snapshot
                    svc.after_step(step, state.kfac_state)
                step += 1
                pending.append(metrics)
                if sup is not None and sup.on_step(step, lambda: state):
                    preempted = True
                    break
                if len(pending) > 2:
                    with tel.span("comm/device_get"):
                        m = jax.device_get(pending.pop(0))
                    eat(m)
            for m in jax.device_get(pending):
                eat(m)
        if preempted:
            if launch.is_primary():
                print(f"elastic: preempted; snapshot at step {step} saved")
            break
        dt = time.perf_counter() - t0
        imgs_per_sec = steps_per_epoch * global_bs * accum / dt
        if launch.is_primary():
            print(
                f"epoch {epoch}: loss={loss_m.avg:.4f} acc={acc_m.avg:.4f} "
                f"lr={lr:.4f} {imgs_per_sec:.0f} img/s ({dt:.1f}s)"
            )
        writer.add_scalar("train/loss", loss_m.avg, epoch)
        writer.add_scalar("train/accuracy", acc_m.avg, epoch)
        writer.add_scalar("train/lr", lr, epoch)
        if nu_n:
            writer.add_scalar("kfac/nu_min", nu_min, epoch)
            writer.add_scalar("kfac/nu_mean", nu_sum / nu_n, epoch)
            writer.add_scalar("kfac/min_damped_eig", eig_min, epoch)
            means = {k: s / c for k, (s, c) in sorted(diag_acc.items())}
            for k, v in means.items():
                # kfac_cond_max -> kfac/cond_max_mean
                writer.add_scalar(f"kfac/{k[5:]}_mean", v, epoch)
            if launch.is_primary():
                print(f"  kfac: nu_min={nu_min:.4f} nu_mean={nu_sum/nu_n:.4f} "
                      f"min_damped_eig={eig_min:.3e}")
                if means:
                    print(
                        "  kfac: "
                        f"cond_max={means.get('kfac_cond_max', 0.0):.3e} "
                        f"upd_cos={means.get('kfac_update_grad_cos', 0.0):.3f} "
                        "stale="
                        f"{means.get('kfac_eigen_stale_steps', 0.0):.1f}"
                    )

        if x_val is not None:
            if bn_recal is not None and x_train is not None:
                for j, (xb, _) in enumerate(data_lib.epoch_batches(
                    x_train, y_train, local_bs, shuffle=True, augment=False,
                    seed=args.seed + 1000 + epoch,
                    num_shards=n_proc, shard_index=launch.rank(),
                )):
                    if j >= args.bn_recal_batches:
                        break
                    state = bn_recal(state, put_global_batch(mesh, (xb,))[0])
            # full-split masked eval: the jitted step reduces over the GLOBAL
            # batch, so the sums below are already pod-wide — no allreduce
            val_bs = args.val_batch_size * world // n_proc
            vl_sum = vc_sum = vn = 0.0
            for xb, yb, mb in data_lib.eval_batches(
                x_val, y_val, val_bs,
                num_shards=n_proc, shard_index=launch.rank(),
            ):
                m = jax.device_get(
                    eval_step(state, put_global_batch(mesh, (xb, yb, mb)))
                )
                vl_sum += float(m["loss_sum"])
                vc_sum += float(m["correct"])
                vn += float(m["count"])
            val_loss, val_acc = vl_sum / vn, vc_sum / vn
            if launch.is_primary():
                print(f"  val: loss={val_loss:.4f} acc={val_acc:.4f}")
            writer.add_scalar("val/loss", val_loss, epoch)
            writer.add_scalar("val/accuracy", val_acc, epoch)

        if tel.enabled:
            # per-phase device cost from step-variant p50 deltas (the step
            # is ONE compiled program; docs/OBSERVABILITY.md explains why
            # in-graph phases can't be timed directly)
            p_plain = tel.percentiles("step/plain")
            p_fac = tel.percentiles("step/factors")
            p_eig = tel.percentiles("step/eigen")
            p_h2d = tel.percentiles("comm/host_to_device")
            if p_plain and p_fac:
                tel.set_gauge(
                    "phase/factor_ms", max(0.0, (p_fac[0] - p_plain[0]) * 1e3)
                )
            if p_fac and p_eig:
                tel.set_gauge(
                    "phase/eigh_ms", max(0.0, (p_eig[0] - p_fac[0]) * 1e3)
                )
            if p_h2d:
                tel.set_gauge("phase/comm_ms", p_h2d[0] * 1e3)
            excess = recompiles.check()
            if excess and launch.is_primary():
                print(f"  WARNING: unexpected recompiles (jit cache over "
                      f"budget): {excess}")
            if launch.is_primary():
                observability.write_prometheus(
                    os.path.join(args.telemetry_dir, "metrics.prom"), tel
                )
            observability.flush_jsonl(tel_writer, tel, epoch)

        if args.checkpoint_dir:
            ckpt.save_checkpoint(args.checkpoint_dir, epoch, state)

    if sup is not None:
        sup.wait()  # join any in-flight background snapshot write
    if tel.enabled:
        # collective on multi-host: every rank calls, rank 0 prints
        table = observability.summary_table(tel)
        if launch.is_primary():
            print("telemetry summary:")
            print(table)
    tel_writer.close()
    writer.close()
    return state


if __name__ == "__main__":
    main()
