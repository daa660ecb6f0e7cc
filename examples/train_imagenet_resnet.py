"""ImageNet ResNet training with distributed K-FAC on TPU (JAX).

Flag-parity port of the reference trainer (examples/pytorch_imagenet_resnet.
py:33-107): label smoothing, 5-epoch warmup, per-epoch checkpointing with
auto-resume (newest-epoch scan + ``KFACParamScheduler(start_epoch=...)``),
damping schedule ×0.5 at {40, 80}. Improvements: K-FAC curvature state is
checkpointed too (the reference loses it on resume, SURVEY.md §3.4), and
resume needs no broadcast step — the restored pytree is device_put with the
replicated sharding.

Data: an ImageFolder-style tree is impractical in this zero-egress image;
the pipeline consumes numpy shards (``--data-dir`` with ``train_x.npy``/
``train_y.npy``/``val_x.npy``/``val_y.npy``, NHWC uint8 raw pixels —
recommended, stored at e.g. 256×256 — or float32 pre-normalized) or
synthetic batches (``--synthetic``). Training applies the reference's full
augmentation stack (RandomResizedCrop(size)+flip; val Resize(--val-resize)+
CenterCrop, pytorch_imagenet_resnet.py:154-193) via the native C++ worker
pool (runtime/native/loader.cpp modes 2/3) with a numpy fallback; uint8
inputs are normalized with the ImageNet stats in the loader.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Callable, List, NamedTuple, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import _env  # noqa: F401  (platform forcing — must precede jax use)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu import KFAC, KFACParamScheduler, capture, runtime
from kfac_pytorch_tpu.compile_cache import RecompileMonitor, expected_step_variants
from kfac_pytorch_tpu.models import imagenet_resnet
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
from kfac_pytorch_tpu.training import evaluation
from kfac_pytorch_tpu.training import profiling
from kfac_pytorch_tpu.training.metrics import Metric, ScalarWriter
from kfac_pytorch_tpu.training.step import kfac_flags_for_step, make_sgd


def parse_args(argv=None):
    # Flag surface mirrors pytorch_imagenet_resnet.py:33-107.
    p = argparse.ArgumentParser(
        description="ImageNet K-FAC Example (TPU/JAX)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--data-dir", default=None, help="numpy-shard data dir")
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--val-resize", type=int, default=256,
                   help="eval shorter-side resize before the center crop")
    p.add_argument("--no-augment", action="store_true",
                   help="disable train augmentation (pass shards through)")
    p.add_argument("--num-workers", type=int, default=4,
                   help="native data-pipeline threads; 0 forces the numpy "
                        "fallback path (pytorch_imagenet_resnet.py's "
                        "DataLoader workers analog)")
    p.add_argument("--log-dir", default="./logs")
    p.add_argument("--checkpoint-dir", default="./checkpoints")
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=32, help="per-device")
    p.add_argument("--batches-per-allreduce", type=int, default=1,
                   help="gradient-accumulation microbatches per optimizer step "
                        "(pytorch_imagenet_resnet.py:44-48)")
    p.add_argument("--val-batch-size", type=int, default=32)
    p.add_argument("--epochs", type=int, default=55)
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--base-lr", type=float, default=0.0125)
    p.add_argument("--lr-decay", nargs="+", type=int, default=[25, 35, 40, 45, 50])
    p.add_argument("--warmup-epochs", type=float, default=5)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=5e-5)
    p.add_argument("--label-smoothing", type=float, default=0.1)
    p.add_argument("--kfac-update-freq", type=int, default=10, help="0 disables K-FAC")
    p.add_argument("--kfac-cov-update-freq", type=int, default=1)
    p.add_argument("--stat-decay", type=float, default=0.95)
    p.add_argument("--damping", type=float, default=0.002)
    p.add_argument("--damping-alpha", type=float, default=0.5)
    p.add_argument("--damping-schedule", nargs="+", type=int, default=[40, 80])
    p.add_argument("--kl-clip", type=float, default=0.001)
    p.add_argument("--diag-blocks", type=int, default=1)
    p.add_argument("--diag-warmup", type=int, default=5)
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
                   help="initialize model weights from a reference/"
                        "torchvision ResNet checkpoint (.pth/.pth.tar, "
                        "bare state_dict or the reference's {'model': ...} "
                        "wrapper); optimizer and K-FAC state start fresh")
    p.add_argument("--precond-comm-dtype", default=None,
                   choices=[None, "bf16"],
                   help="downcast the distributed-precondition psum payload "
                        "(the reference's --fp16-allreduce compression, "
                        "applied to the preconditioned-grad exchange)")
    p.add_argument("--grad-comm-dtype", default=None, choices=[None, "bf16"],
                   help="downcast the per-step data-parallel gradient mean "
                        "on the wire (the reference's --fp16-allreduce on "
                        "DistributedOptimizer); None = exact f32 reduction")
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
    p.add_argument("--seed", type=int, default=42)
    return p.parse_args(argv)


def _npy_shards(data_dir, split):
    xp = os.path.join(data_dir, f"{split}_x.npy")
    yp = os.path.join(data_dir, f"{split}_y.npy")
    if os.path.isfile(xp) and os.path.isfile(yp):
        return np.load(xp, mmap_mode="r"), np.load(yp)
    return None


class Training(NamedTuple):
    """What :func:`build` makes of the flags on a mesh."""

    kfac: Optional[KFAC]
    init_state: Callable[[], TrainState]
    train_step: Callable
    eval_step: Callable


class TrainRun(NamedTuple):
    """What :func:`main` hands back: the final state and what the run saw."""

    state: TrainState
    step_losses: List[float]  # every step's loss, fetched from the device
    kfac: Optional[KFAC]
    train_step: Callable
    batch_sharding: Optional[jax.sharding.Sharding]  # of the last batch fed


def build(args, mesh) -> Training:
    """Preconditioner, initial state and jitted step for ``args`` on ``mesh``
    (global batch = ``--batch-size`` x mesh size). Without
    ``--init-from-torch`` ``init_state`` is traceable, so
    ``jax.eval_shape(init_state)`` gives the state's shapes without
    materializing it (scripts/compile_for_chip.py)."""
    world = mesh.devices.size
    model = imagenet_resnet.get_model(
        args.model, dtype=jnp.bfloat16 if args.bf16 else None
    )
    im = args.image_size
    init_images = jnp.zeros((args.batch_size * world, im, im, 3), jnp.float32)
    tx = make_sgd(momentum=args.momentum, weight_decay=args.wd)

    kfac = None
    if args.kfac_update_freq > 0:
        kfac = KFAC(
            layers=capture.discover_layers(model, init_images, train=True),
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
            factor_kernel=args.factor_kernel,
        )

    def init_state():
        variables = model.init(
            jax.random.PRNGKey(args.seed), init_images, train=True
        )
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
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=tx.init(params),
            kfac_state=kfac.init(params) if kfac else None,
        )

    train_step = make_train_step(
        model, tx, kfac, label_smoothing=args.label_smoothing,
        train_kwargs={"train": True}, accum_steps=args.batches_per_allreduce,
        mesh=mesh if args.grad_comm_dtype else None,
        grad_comm_dtype=jnp.bfloat16 if args.grad_comm_dtype == "bf16" else None,
    )
    eval_step = make_masked_eval_step(
        model, label_smoothing=args.label_smoothing, eval_kwargs={"train": False}
    )
    return Training(kfac, init_state, train_step, eval_step)


def main(argv=None) -> TrainRun:
    args = parse_args(argv)
    if args.val_resize < args.image_size:
        raise SystemExit(
            f"--val-resize ({args.val_resize}) must be >= --image-size "
            f"({args.image_size}): Resize(shorter side) must cover the "
            "CenterCrop (the transform stack replicates borders otherwise, "
            "silently diverging from the reference's torchvision behavior)"
        )

    launch.initialize()  # multi-host wiring; no-op single-process
    mesh = data_parallel_mesh()
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

    kfac, init_state, train_step, eval_step = build(args, mesh)
    state = init_state()
    im = args.image_size
    lr_base = args.base_lr * world
    kfac_sched = None
    recompiles = RecompileMonitor()
    # legitimate variant counts: plain/factors/factors+eigen, x2 while a
    # diag_warmup schedule is active (compile_cache.expected_step_variants)
    recompiles.watch("train_step", train_step, expected_step_variants(kfac))

    resume_from_epoch = 0
    if args.checkpoint_dir:
        state, resume_from_epoch = ckpt.auto_resume(args.checkpoint_dir, state)
        # all hosts must agree on the epoch (the reference broadcasts it,
        # pytorch_imagenet_resnet.py:136-140)
        resume_from_epoch = int(launch.broadcast_host_value(resume_from_epoch))
        # checked only AFTER the broadcast: raising on a subset of hosts
        # (host-local checkpoint dirs) would leave the others hanging in
        # the collective
        if resume_from_epoch and args.init_from_torch:
            raise SystemExit(
                f"--init-from-torch was given but {args.checkpoint_dir} "
                f"holds an epoch-{resume_from_epoch - 1} checkpoint that "
                "auto-resume just restored over the migrated weights; "
                "point --checkpoint-dir at a fresh directory to start from "
                "the torch checkpoint, or drop --init-from-torch to resume"
            )
        if resume_from_epoch and launch.is_primary():
            print(f"resumed from epoch {resume_from_epoch - 1}")
    if kfac:
        # scheduler restores its position from the resume epoch
        # (pytorch_imagenet_resnet.py:228-234)
        kfac_sched = KFACParamScheduler(
            kfac,
            damping_alpha=args.damping_alpha,
            damping_schedule=args.damping_schedule,
            update_freq_alpha=args.kfac_update_freq_alpha,
            update_freq_schedule=args.kfac_update_freq_schedule,
            start_epoch=resume_from_epoch,
        )

    state = jax.device_put(state, NamedSharding(mesh, P()))

    lr_factor = create_lr_schedule(world, args.warmup_epochs, args.lr_decay)

    train_data = None if args.synthetic else (
        _npy_shards(args.data_dir, "train") if args.data_dir else None
    )
    val_data = None if args.synthetic else (
        _npy_shards(args.data_dir, "val") if args.data_dir else None
    )
    # host-agreement collectives (same contract as the CIFAR trainer): every
    # host must make the data/pipeline decisions identically or the pod
    # desyncs — see train_cifar10_resnet.py for the full rationale.
    all_have_data = bool(launch.host_min(train_data is not None))
    if train_data is not None and not all_have_data:
        print(f"host {launch.rank()}: data found but other hosts lack it; using --synthetic")
        train_data = val_data = None
    # the eval loop runs pod-global collectives, so val presence must be
    # host-agreed too — a host missing only val shards must not desync
    if not bool(launch.host_min(val_data is not None)):
        if val_data is not None:
            print(f"host {launch.rank()}: val shards found but other hosts lack them; skipping eval")
        val_data = None
    augment = not args.no_augment
    use_native = bool(
        launch.host_min(
            all_have_data and args.num_workers > 0 and runtime.native_available()
        )
    )

    train_loader = None
    if train_data is not None:
        x_train, y_train = train_data
        uint8 = x_train.dtype == np.uint8
        stored = tuple(x_train.shape[1:3])
        steps_per_epoch = len(x_train) // (global_bs * accum)
        # the reference train stack is RandomResizedCrop(size)+flip
        # (pytorch_imagenet_resnet.py:154-166); without augmentation,
        # same-size shards pass through (uint8 still decodes+normalizes in
        # mode 'none') and anything else center-crops
        if augment:
            train_mode = "rrc"
        elif stored == (im, im):
            train_mode = "none"
        else:
            train_mode = "centercrop"
        norm = dict(mean=data_lib.IMAGENET_MEAN, std=data_lib.IMAGENET_STD) if uint8 else {}
        if use_native:
            train_loader = runtime.NativeEpochLoader(
                x_train, y_train, local_bs * accum, shuffle=True,
                num_shards=n_proc, shard_index=launch.rank(),
                mode=train_mode, out_size=(im, im),
                resize_size=args.val_resize, copy=False,
                num_workers=args.num_workers, **norm,
            )
        if launch.is_primary():
            print(
                f"ImageNet shards: {len(x_train)} train / "
                f"{len(val_data[0]) if val_data else 0} val, stored {stored} "
                f"{x_train.dtype}, train={train_mode} "
                f"({'native' if train_loader else 'numpy'} pipeline)"
            )
    else:
        if not args.synthetic:
            print("no data found; falling back to --synthetic")
        steps_per_epoch = args.steps_per_epoch or 100
    if args.steps_per_epoch:
        steps_per_epoch = min(steps_per_epoch, args.steps_per_epoch)

    writer = ScalarWriter(args.log_dir, enabled=jax.process_index() == 0)
    step = int(jax.device_get(state.step))
    step_losses = []
    batch = None

    for epoch in range(resume_from_epoch, args.epochs):
        if kfac_sched:
            kfac_sched.step(epoch=epoch)
        if train_loader is not None:
            batch_iter = train_loader.epoch(args.seed + epoch)
        elif train_data is not None:
            x_train, y_train = train_data
            # numpy fallback: same seeded permutation on every host;
            # interleaved slice per host (the DistributedSampler pattern)
            rng = np.random.RandomState(args.seed + epoch)
            order = rng.permutation(
                len(x_train) // global_bs * global_bs
            )[launch.rank() :: n_proc]

            def batches():
                n = local_bs * accum
                for b in range(steps_per_epoch):
                    take = np.sort(order[b * n : (b + 1) * n])  # mmap-friendly
                    xb, yb = x_train[take], np.asarray(y_train[take], np.int32)
                    if train_mode == "rrc":
                        xb = data_lib.imagenet_train_augment(xb, im, rng)
                    elif train_mode == "centercrop":
                        xb = data_lib.imagenet_eval_transform(
                            xb, im, resize_size=args.val_resize
                        )
                    elif xb.dtype == np.uint8:
                        # pass-through still decodes + normalizes uint8
                        xb = (
                            np.asarray(xb, np.float32) / 255.0
                            - data_lib.IMAGENET_MEAN
                        ) / data_lib.IMAGENET_STD
                    else:
                        xb = np.asarray(xb, np.float32)
                    yield xb, yb

            batch_iter = batches()
        else:
            batch_iter = data_lib.synthetic_batches(
                local_bs * accum, (im, im, 3), 1000, steps_per_epoch, seed=args.seed
            )

        t0 = time.perf_counter()
        loss_m, acc_m = Metric("train/loss"), Metric("train/accuracy")

        def eat(m):
            step_losses.append(float(m["loss"]))
            loss_m.update(m["loss"])
            acc_m.update(m["accuracy"])

        # lag-window metric fetch: async dispatch, bounded in-flight batches
        pending = []
        with profiling.maybe_trace(args.log_dir, args.profile_epoch == epoch):
            for i, (xb, yb) in enumerate(batch_iter):
                if i >= steps_per_epoch:
                    break
                lr = lr_base * lr_factor(epoch + i / steps_per_epoch)
                flags = kfac_flags_for_step(step, kfac, epoch)
                batch = put_global_batch(mesh, (xb, yb), accum_steps=accum)
                state, metrics = train_step(
                    state, batch, jnp.float32(lr),
                    jnp.float32(kfac.hparams.damping if kfac else 0.0), **flags
                )
                step += 1
                pending.append(metrics)
                if len(pending) > 2:
                    eat(jax.device_get(pending.pop(0)))
            for m in jax.device_get(pending):
                eat(m)
        dt = time.perf_counter() - t0
        if launch.is_primary():
            print(
                f"epoch {epoch}: loss={loss_m.avg:.4f} acc={acc_m.avg:.4f} "
                f"lr={lr:.4f} {steps_per_epoch * global_bs * accum / dt:.0f} img/s"
            )
        writer.add_scalar("train/loss", loss_m.avg, epoch)
        writer.add_scalar("train/accuracy", acc_m.avg, epoch)
        writer.add_scalar("train/lr", lr, epoch)

        if val_data is not None:
            x_val, y_val = val_data
            # full-split masked eval (training/evaluation.py — shared with
            # examples/evaluate.py); jitted sums are already pod-global
            val_loss, val_acc = evaluation.run_imagenet_validation(
                eval_step, mesh, state, x_val, y_val,
                image_size=im, val_resize=args.val_resize,
                local_batch=args.val_batch_size * world // n_proc,
                n_proc=n_proc, rank=launch.rank(),
                use_native=use_native, num_workers=args.num_workers,
            )
            if launch.is_primary():
                print(f"  val: loss={val_loss:.4f} acc={val_acc:.4f}")
            writer.add_scalar("val/loss", val_loss, epoch)
            writer.add_scalar("val/accuracy", val_acc, epoch)

        if args.checkpoint_dir:
            ckpt.save_checkpoint(args.checkpoint_dir, epoch, state)
        excess = recompiles.check()
        if excess and launch.is_primary():
            print(f"WARNING: unexpected recompiles: {excess}")

    writer.close()
    return TrainRun(
        state, step_losses, kfac, train_step,
        None if batch is None else batch[0].sharding,
    )


if __name__ == "__main__":
    main()
