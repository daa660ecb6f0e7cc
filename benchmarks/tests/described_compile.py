"""Compile a cell's step programs at full size for a DESCRIBED v5e, no chip
(rehearsal 3 of the on-chip-measurement guide), with ``memory_analysis()``.
Nothing runs: this gives no time and no result, only what the chip's compiler
accepts and how much memory each program needs.

    JAX_PLATFORMS=cpu python benchmarks/tests/described_compile.py <cell> [program ...]

Programs: refresh, factors, plain, twin, and the reference's device half of a step
with and without capture (reference_capture, reference_next), its inverses
(reference_inverses) and its second half (reference_update).

    JAX_PLATFORMS=cpu python benchmarks/tests/described_compile.py --rehearsal <config> [groups]

compiles the programs of the reference alone (``reference/kfac_sgd.py::Steps``)
over the rehearsal stack of ``tests/configs/<config>.json`` with its layers in
``groups`` groups: the plain first half, the first group's capture pass (with
the gradients), the second group's (without), one group's inverses and
preconditioning, and the KL clip with SGD.
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "examples"), HERE]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import run as bench  # noqa: E402

FLAGS = {
    "plain": dict(update_factors=False, update_eigen=False),
    "factors": dict(update_factors=True, update_eigen=False),
    "refresh": dict(update_factors=True, update_eigen=True),
}


def report_compiled(label, name, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    print(json.dumps({
        **label, "program": name, "compile_seconds": round(time.perf_counter() - t0, 1),
        "argument_bytes": mem.argument_size_in_bytes, "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes, "alias_bytes": mem.alias_size_in_bytes,
        "live_bytes": mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "host_peak_rss_gib": round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 2),
    }), flush=True)


def rehearsal(name, groups=None):
    """The reference's own programs over the rehearsal stack, a group at a time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    tests = os.path.join(HERE, "tests")
    cfg = bench.load_json(tests, "configs", name + ".json")
    kf = bench.load_module(bench.HERE, "reference", "kfac_sgd.py")
    model = bench.load_module(tests, "reference", cfg["reference"] + ".py").Model(cfg)
    hyper = kf.hyper_of(cfg)
    steps = kf.Steps(model, hyper, groups=int(groups or cfg.get("reference_layer_groups", 1)))
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), t)
    params = on_chip(model.param_shapes())
    state = on_chip(jax.eval_shape(lambda p: kf.init_state(model, p), params))
    ids = jax.ShapeDtypeStruct((cfg["per_chip_batch"], cfg["seq_len"]), jnp.int32, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    label = {"config": name, "groups": len(steps.groups)}
    if len(steps.groups) == 1:
        report_compiled(label, "capture", steps.first_half[True].lower(state, (ids, ids)))
        report_compiled(label, "inverses", steps.inverses.lower(state.factors))
        report_compiled(label, "update", steps.second_half.lower(state, params, state.factors, state.inverses, scalar))
        return
    of_group = lambda tree, i: {layer["name"]: tree[layer["name"]] for layer in steps.groups[i]}
    bare = kf.RefState(params, None, None, None)
    report_compiled(label, "plain", steps.plain.lower(bare, (ids, ids)))
    for i in (0, 1):
        report_compiled(label, f"capture_group{i}",
                        steps.capture[i].lower(bare._replace(factors=of_group(state.factors, i)), (ids, ids)))
    report_compiled(label, "inverses_group0", steps.inverses.lower(of_group(state.factors, 0)))
    report_compiled(label, "precondition_group0", steps.precondition[0].lower(params, of_group(state.inverses, 0)))
    report_compiled(label, "clip_and_sgd", steps.finish.lower(params, params, params, scalar, scalar))


def main(argv):
    from jax.experimental import topologies

    from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    if argv[0] == "--rehearsal":
        return rehearsal(*argv[1:])
    cell = bench.load_cell(argv[0])
    programs = argv[1:] or ["refresh", "factors", "plain", "twin", "reference_capture", "reference_next",
                            "reference_inverses", "reference_update"]
    cfg, mix = cell["cfg"], cell["traffic_mix"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = data_parallel_mesh(topo.devices[: cell["chips"]])
    replicated = NamedSharding(mesh, P())
    shard = lambda sharding: (lambda t: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), t))
    builder = bench.load_module(bench.HERE, "configs", cfg["builder"] + ".py")
    built = builder.build(cfg, mix, mesh)
    batch = shard(NamedSharding(mesh, P("data")))(built["batch_struct"])
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)
    state = shard(replicated)(jax.eval_shape(built["init_state"]))

    report = lambda name, lowered: report_compiled({"cell": cell["name"]}, name, lowered)

    for name in programs:
        if name in FLAGS:
            flags = dict(FLAGS[name], diag_warmup_done=True)
            report(name, built["train_step"].lower(state, batch, scalar, scalar, **flags))
        elif name == "twin":
            twin = builder.build(cfg, mix, mesh, kfac_on=False)
            tstate = shard(replicated)(jax.eval_shape(twin["init_state"]))
            report(name, twin["train_step"].lower(tstate, batch, scalar, scalar, **FLAGS["plain"]))
        else:
            kf = bench.load_module(bench.HERE, "reference", "kfac_sgd.py")
            model = bench.load_module(bench.HERE, "reference", cfg["reference"] + ".py").Model(cfg, mix)
            hyper = kf.hyper_of(cfg)
            params = jax.eval_shape(built["init_state"]).params
            rstate = shard(replicated)(jax.eval_shape(lambda p: kf.init_state(model, p), params))
            if name == "reference_inverses":
                fn = jax.jit(lambda f: kf.damped_inverses(f, hyper["damping"]))
                report(name, fn.lower(rstate.factors))
            elif name == "reference_update":
                fn = jax.jit(lambda st, g, f, i, lr: kf.precondition_and_update(model, hyper, st, g, f, i, lr))
                report(name, fn.lower(rstate, rstate.params, rstate.factors, rstate.inverses, scalar))
            else:
                uf = name == "reference_capture" or 1 % mix["fac_update_freq"] == 0
                fn = jax.jit(lambda st, b: kf.forward_backward(
                    model, hyper, st, b, update_factors=uf,
                    row_blocks=cell["file"].get("reference_row_blocks", 1)))
                report(name, fn.lower(rstate, shard(replicated)(built["batch_struct"])))

if __name__ == "__main__":
    main(sys.argv[1:])
