"""First steps on the chip: the K-FAC ResNet-50 ImageNet trainer on one v5e.

    python chip_smoke.py            # one chip: the trainer's own entry point
    python chip_smoke.py --chips 4  # four chips: data-parallel K-FAC vs one device

One process, the one that owns the chip; it starts no other. Without a TPU
it exits non-zero before compiling anything. Every phase raises on failure
(nothing here catches), so a failed check is a non-zero exit and no result
line. The last line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One chip: ``examples/train_imagenet_resnet.py::main`` at full width, depth
and image size (ResNet-50, 224x224, per-device batch 32, synthetic data made
from the seed), default kernels, on a schedule short enough that every step
program of the path — plain, +factors, +factors+refresh — runs. Checked:
finite losses for every step, the first of them against the same forward
pass on the CPU backend; the step programs compiled; finite final state; a
checkpoint written; peak device memory reported.

Four chips (``--chips 4``, that phase only): the same entry on all four
local chips (``data_parallel_mesh``, ``KFAC(mesh=mesh)``, per-device batch
32) against the same seed and the same global batch of 128 through the same
``make_train_step`` on one of the four devices.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "examples"))

import _env  # noqa: E402,F401  (places the compile cache; must precede jax use)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

SEED = 42
# git-ignored; checkpoints (GBs of curvature state) stay out of chiprun_out/
WORK_DIR = os.path.join(ROOT, ".chip_smoke")
# step i: factors on even steps, refresh on steps 0 and 4, plain on odd steps
SCHEDULE = ("--kfac-update-freq", "4", "--kfac-cov-update-freq", "2")
STEPS = 6
# Compile seconds per step program for a DESCRIBED v5e: scripts/
# compile_for_chip.py on the sandbox's host, jax 0.9.0 (PR 23; docs/PERF.md,
# "First chip run"). Compiled, not run: these are not device numbers.
COMPILED_FOR_DESCRIBED_CHIP = {
    "inverse": {"plain": 45.8, "factors": 98.7, "refresh": 338.5},
    "eigen": {"refresh": 1510.4},
}
# The default eigen path is the first choice, but its refresh program alone
# does not compile inside this script's 1200 s limit from a cold cache, so
# the smoke keeps the model whole and takes the trainer's existing full-width
# path whose refresh does: --precond-method inverse (the Cholesky path).
PRECOND_METHOD = "inverse"
PRECOND_WHY = (
    "the eigen path's refresh program alone took {eigen} s to compile for a "
    "described v5e, past this script's 1200 s limit from a cold cache; the "
    "inverse (Cholesky) path's three programs took {inverse} s"
)
# Chip vs CPU, and four chips vs one device: same math, different reduction
# order, on an MXU whose default matmul precision is one bf16 pass — close,
# not bitwise.
LOSS_RTOL = 2e-2


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero unless it is ``chips``
    TPU chips. Called before anything is compiled."""
    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if found["platform"] != "tpu" or found["count"] != chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chip(s), JAX found {found}")
    return found


class CompileClock:
    """What JAX reports about compilation: seconds spent in the backend
    compiler, and programs it took from the persistent cache instead."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.durations = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == self.COMPILE:
            self.durations.append(seconds)

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def stop(self):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def trainer_argv(*extra, model="resnet50", image_size=224, batch_size=32):
    """Flags for ``train_imagenet_resnet.main``: nothing about the model is
    cut; only the schedule is short. (The keywords are for the CPU
    rehearsals in tests/test_chip_smoke.py; the script passes none.)"""
    return [
        "--synthetic", "--model", model,
        "--image-size", str(image_size), "--val-resize", str(max(256, image_size)),
        "--batch-size", str(batch_size),
        "--precond-method", PRECOND_METHOD,
        *SCHEDULE,
        "--epochs", "1", "--steps-per-epoch", str(STEPS),
        "--seed", str(SEED),
        "--checkpoint-dir", os.path.join(WORK_DIR, "checkpoints"),
        "--log-dir", os.path.join(WORK_DIR, "logs"),
        *extra,
    ]


def step_programs(kfac):
    """Which step program each step of the schedule runs (host-side flags)."""
    from kfac_pytorch_tpu.training.step import kfac_flags_for_step

    flags = [kfac_flags_for_step(s, kfac, 0) for s in range(STEPS)]
    return [
        "refresh" if f["update_eigen"]
        else "factors" if f["update_factors"] else "plain"
        for f in flags
    ]


def check_losses(losses):
    assert len(losses) == STEPS, f"expected {STEPS} step losses, got {losses}"
    assert all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}"


def run_trainer(argv):
    """Run the trainer's own ``main`` in this process and check what it
    hands back. Returns ``(TrainRun, report dict)``."""
    import train_imagenet_resnet as trainer

    shutil.rmtree(WORK_DIR, ignore_errors=True)  # a stale checkpoint would resume
    clock = CompileClock()
    t0 = time.perf_counter()
    run = trainer.main(argv)
    jax.block_until_ready(run.state)
    wall = time.perf_counter() - t0
    clock.stop()

    check_losses(run.step_losses)
    assert int(run.state.step) == STEPS, int(run.state.step)
    programs = step_programs(run.kfac)
    assert set(programs) == {"plain", "factors", "refresh"}, programs
    compiled = run.train_step._cache_size()
    assert compiled == len(set(programs)), (
        f"{compiled} step programs compiled for {sorted(set(programs))}"
    )
    assert run.kfac.factor_kernel == "dense"
    all_finite = jax.jit(lambda tree: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)])))
    assert bool(all_finite(run.state)), "non-finite state"
    assert os.listdir(os.path.join(WORK_DIR, "checkpoints")), "no checkpoint written"

    stats = jax.local_devices()[0].memory_stats() or {}
    report = {
        "argv": argv,
        "precond_method": run.kfac.precond_method,
        "factor_kernel": run.kfac.factor_kernel,
        "step_programs": programs,
        "step_losses": run.step_losses,
        "step_programs_compiled": compiled,
        "backend_compiles": len(clock.durations),
        "persistent_cache_hits": clock.cache_hits,
        "compile_seconds": round(sum(clock.durations), 1),
        # the step programs; the rest are the trainer's eager set-up ops
        "compile_seconds_over_5s": [round(d, 1) for d in clock.durations if d > 5],
        "wall_seconds": round(wall, 1),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    return run, report


def cpu_first_loss(argv):
    """Step 0's loss recomputed on the CPU backend, in f32, by the repo's own
    model and loss: same seed, same first batch, parameters as initialised
    (the step's loss is taken before its update). An independent backend's
    answer for the full-size forward pass."""
    import train_imagenet_resnet as trainer
    from kfac_pytorch_tpu.models import imagenet_resnet
    from kfac_pytorch_tpu.training import data as data_lib
    from kfac_pytorch_tpu.training.step import softmax_cross_entropy

    args = trainer.parse_args(argv)
    model = imagenet_resnet.get_model(args.model)
    im = args.image_size
    x, y = next(data_lib.synthetic_batches(
        args.batch_size, (im, im, 3), 1000, 1, seed=args.seed))
    with jax.default_device(jax.devices("cpu")[0]):
        variables = model.init(
            jax.random.PRNGKey(args.seed), jnp.zeros_like(x), train=True)
        logits, _ = jax.jit(
            lambda v, x: model.apply(v, x, train=True, mutable=["batch_stats"])
        )(variables, x)
        return float(softmax_cross_entropy(logits, y, args.label_smoothing))


def one_chip(**kw):
    argv = trainer_argv(**kw)
    described = COMPILED_FOR_DESCRIBED_CHIP
    print(f"chip_smoke: K-FAC path = --precond-method {PRECOND_METHOD}: "
          + PRECOND_WHY.format(
              eigen=described["eigen"]["refresh"],
              inverse=" + ".join(map(str, described["inverse"].values()))))
    print("chip_smoke: compile seconds for a described v5e (compiled, not "
          "run): " + json.dumps(described), flush=True)
    _, report = run_trainer(argv)
    report["cpu_first_loss"] = cpu_first_loss(argv)
    report["loss_rtol"] = LOSS_RTOL
    np.testing.assert_allclose(
        report["step_losses"][0], report["cpu_first_loss"], rtol=LOSS_RTOL)
    return report


def one_device_losses(argv, global_batch, world, device):
    """The comparison: same seed, same global batch, the same
    ``make_train_step`` (through the trainer's ``build``) on ONE device."""
    import train_imagenet_resnet as trainer
    from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh, put_global_batch
    from kfac_pytorch_tpu.training import create_lr_schedule
    from kfac_pytorch_tpu.training import data as data_lib
    from kfac_pytorch_tpu.training.step import kfac_flags_for_step

    args = trainer.parse_args(argv)
    args.batch_size = global_batch
    mesh = data_parallel_mesh([device])
    kfac, init_state, train_step, _ = trainer.build(args, mesh)
    assert kfac.mesh is None
    with jax.default_device(device):
        state = jax.device_put(init_state(), NamedSharding(mesh, P()))
        lr_factor = create_lr_schedule(world, args.warmup_epochs, args.lr_decay)
        im = args.image_size
        losses = []
        for i, (xb, yb) in enumerate(data_lib.synthetic_batches(
                global_batch, (im, im, 3), 1000, STEPS, seed=args.seed)):
            lr = args.base_lr * world * lr_factor(i / STEPS)
            state, metrics = train_step(
                state, put_global_batch(mesh, (xb, yb)), jnp.float32(lr),
                jnp.float32(kfac.hparams.damping),
                **kfac_flags_for_step(i, kfac, 0),
            )
            losses.append(float(metrics["loss"]))
    return losses


def check_spread(run, world):
    """The work is really spread: the batch lives on every device, and the
    layer-to-device table of the distributed preconditioning
    (parallel/assignment.py, as ``KFAC.update`` builds it) names several
    owners."""
    from kfac_pytorch_tpu.parallel.assignment import precondition_assignment

    assert len(run.batch_sharding.device_set) == world, run.batch_sharding
    kfac = run.kfac
    assert kfac.mesh is not None and kfac.mesh.devices.size == world
    assert kfac.distribute_precondition
    shapes = {
        name: (f["G"].shape[0], f["A"].shape[0])
        for name, f in run.state.kfac_state["factors"].items()
    }
    owners = precondition_assignment(shapes, world)
    assert len(set(owners.values())) > 1, owners
    return sorted(set(owners.values()))


def four_chips(world=4, batch_size=32, **kw):
    """The trainer on ``world`` devices against one device.

    The step is one GSPMD program over a global batch axis (gradients and
    factor statistics reduce across devices; batch-norm statistics cover all
    ``world`` x 32 images on both sides), and ``--distribute-precondition``
    gives each layer's every-step solve to one owner device with a psum to
    reassemble. On the inverse path that is the K-FAC work that is spread:
    its Cholesky refresh runs replicated (preconditioner.py), unlike the
    eigen path's sharded eigendecompositions, which PRECOND_WHY rules out
    here. The one-device twin gets the same flag, where it has no effect."""
    argv = trainer_argv("--distribute-precondition", batch_size=batch_size, **kw)
    run, report = run_trainer(argv)
    report["owners"] = check_spread(run, world)
    del run  # frees the replicated state before the one-device run
    ref = one_device_losses(argv, batch_size * world, world, jax.devices()[0])
    check_losses(ref)
    report["one_device_losses"] = ref
    report["loss_rtol"] = LOSS_RTOL
    np.testing.assert_allclose(report["step_losses"], ref, rtol=LOSS_RTOL)
    return report


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4])
    chips = p.parse_args(argv).chips
    device = require_tpu(chips)
    print(f"chip_smoke: {device}; ResNet-50, 224x224, per-device batch 32, "
          f"{STEPS} steps, schedule {' '.join(SCHEDULE)}", flush=True)
    cache_dir = jax.config.jax_compilation_cache_dir
    print(f"chip_smoke: compile cache = {cache_dir} "
          f"(JAX_COMPILATION_CACHE_DIR set: {'JAX_COMPILATION_CACHE_DIR' in os.environ}), "
          f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
          "entries at start", flush=True)
    report = one_chip() if chips == 1 else four_chips()
    assert report["peak_bytes_in_use"], "the TPU reported no peak_bytes_in_use"
    for k, v in report.items():
        print(f"chip_smoke: {k} = {json.dumps(v)}")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"chip_smoke_{chips}chip.json"), "w") as f:
        json.dump({"device": device, **report}, f, indent=1)
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
