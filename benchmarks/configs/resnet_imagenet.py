"""Builder for ImageNet ResNet configurations: the program's own entry,
``examples/train_imagenet_resnet.py::build``, called with the flags a user
would pass. Nothing of the model is assembled here."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _argv(cfg, traffic, kfac_on, bf16):
    k = cfg["kfac"]
    argv = [
        "--synthetic",
        "--model", cfg["model"],
        "--image-size", str(cfg["image_size"]),
        "--val-resize", str(max(256, cfg["image_size"])),
        "--batch-size", str(traffic["per_chip_batch"]),
        "--base-lr", str(cfg["base_lr"]),
        "--momentum", str(cfg["momentum"]),
        "--wd", str(cfg["weight_decay"]),
        "--label-smoothing", str(cfg["label_smoothing"]),
        "--kfac-update-freq", str(traffic["kfac_update_freq"] if kfac_on else 0),
        "--kfac-cov-update-freq", str(traffic["fac_update_freq"]),
        "--stat-decay", str(k["stat_decay"]),
        "--damping", str(k["damping"]),
        "--kl-clip", str(k["kl_clip"]),
        "--precond-method", k["precond_method"],
    ]
    if bf16:
        argv.append("--bf16")
    return argv


def build(cfg, traffic, mesh, kfac_on=True, lower_precision=False):
    """``lower_precision=True`` switches on the program's own bfloat16
    compute path (``--bf16``): the control of the output check."""
    import train_imagenet_resnet as trainer  # examples/ is on sys.path (run.py)

    args = trainer.parse_args(_argv(cfg, traffic, kfac_on, lower_precision))
    training = trainer.build(args, mesh)
    n = traffic["per_chip_batch"] * mesh.devices.size
    im = cfg["image_size"]
    return {
        "kfac": training.kfac,
        "init_state": training.init_state,
        "train_step": training.train_step,
        "batch_struct": (
            jax.ShapeDtypeStruct((n, im, im, cfg["image_channels"]), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ),
        # the trainer's epoch past --diag-warmup: the steady-state programs
        "epoch": args.diag_warmup,
    }
