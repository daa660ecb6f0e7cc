"""Compile the ImageNet trainer's step programs for a DESCRIBED v5e, no chip.

Rehearsal 3 of the on-chip-measurement guide: the TPU compiler is installed
next to the CPU backend and compiles for a chip that is described and not
attached. This hands the described ``v5e:2x2`` devices and the
``jax.eval_shape`` shapes of the trainer's own state
(``examples/train_imagenet_resnet.py::build``) to the real step programs —
plain, +factors, +factors+refresh — and prints each one's compile seconds
and ``memory_analysis()``. Nothing runs: a compile that passes here is NOT a
chip run and gives no time, rate or utilization.

    JAX_PLATFORMS=cpu python scripts/compile_for_chip.py                # one chip
    JAX_PLATFORMS=cpu python scripts/compile_for_chip.py --chips 4      # the 2x2 mesh
    ... --programs refresh -- --precond-method inverse

Flags after ``--`` go to the trainer, whose defaults are the real size
(ResNet-50, 224x224, per-device batch 32).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "examples"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

# step-program name -> the static flags that select it (training/step.py)
PROGRAMS = {
    "plain": dict(update_factors=False, update_eigen=False),
    "factors": dict(update_factors=True, update_eigen=False),
    "refresh": dict(update_factors=True, update_eigen=True),
}


def compile_programs(trainer_argv, chips, programs):
    """Yield ``(name, seconds, memory_analysis, hlo_text)`` per program."""
    from jax.experimental import topologies

    import train_imagenet_resnet as t
    from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh

    # described-chip executables can be written to the persistent cache but
    # never read back without a chip; keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = data_parallel_mesh(topo.devices[:chips])
    args = t.parse_args(trainer_argv)
    training = t.build(args, mesh)
    replicated = NamedSharding(mesh, P())

    def with_sharding(sharding):
        return lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    state = jax.tree_util.tree_map(
        with_sharding(replicated), jax.eval_shape(training.init_state)
    )
    n, im = args.batch_size * chips, args.image_size
    batch = jax.tree_util.tree_map(
        with_sharding(NamedSharding(mesh, P("data"))),
        (jax.ShapeDtypeStruct((n, im, im, 3), jnp.float32),
         jax.ShapeDtypeStruct((n,), jnp.int32)),
    )
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)
    for name in programs:
        # epoch 0, as in the trainer's first steps (kfac_flags_for_step)
        flags = dict(PROGRAMS[name], diag_warmup_done=training.kfac.diag_warmup <= 0)
        t0 = time.perf_counter()
        compiled = training.train_step.lower(
            state, batch, scalar, scalar, **flags
        ).compile()
        yield name, time.perf_counter() - t0, compiled.memory_analysis(), compiled.as_text()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4])
    p.add_argument("--programs", default="plain,factors,refresh")
    p.add_argument("trainer_argv", nargs="*",
                   help="flags for examples/train_imagenet_resnet.py")
    a = p.parse_args(argv)
    print(f"described v5e:2x2, chips={a.chips}, trainer flags: "
          f"{' '.join(a.trainer_argv) or '(defaults)'} (compiled, not run)",
          flush=True)
    for name, secs, mem, hlo in compile_programs(
        a.trainer_argv, a.chips, a.programs.split(",")
    ):
        print(json.dumps({
            "program": name,
            "chips": a.chips,
            "compile_seconds": round(secs, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "tpu_custom_calls": hlo.count("tpu_custom_call"),
            "all_reduces": hlo.count(" all-reduce("),
            "all_gathers": hlo.count(" all-gather("),
        }), flush=True)


if __name__ == "__main__":
    main()
