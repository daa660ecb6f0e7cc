"""The trace-time gauges of a benchmark cell's step programs, at the cell's
own size: each program is built by the cell's builder and traced (not
lowered, not compiled) with telemetry on, and the gauges it set are printed,
one JSON line a program (``kfac/apply_bank_routed``,
``kfac/refresh_sweep_pivots``, ``kfac/capture_*``,
``attention/flash_*``, ``loss/closed_form_calls``; docs/OBSERVABILITY.md).
Tracing at full size takes host memory: run it through the chip tool.

    python scripts/program_gauges.py <cell> [plain,factors,refresh,twin] [benchmark file]

The benchmark file (default ``BENCHMARK.json``) may be a tiny one of
``benchmarks/tests/``, whose traffic lies beside it.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "examples"), os.path.join(ROOT, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run as bench  # noqa: E402
from kfac_pytorch_tpu.observability.telemetry import configure, get_telemetry  # noqa: E402
from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh  # noqa: E402

FLAGS = {
    "plain": dict(update_factors=False, update_eigen=False),
    "factors": dict(update_factors=True, update_eigen=False),
    "refresh": dict(update_factors=True, update_eigen=True),
}


def main(name, programs="plain,factors,refresh,twin", benchmark=None):
    if benchmark:
        cell = bench.load_cell(name, bench.load_json(benchmark), base=os.path.dirname(os.path.abspath(benchmark)))
    else:
        cell = bench.load_cell(name)
    cfg, mix = cell["cfg"], cell["traffic_mix"]
    mesh = data_parallel_mesh(jax.devices()[: cell["chips"]])
    builder = bench.load_module(bench.HERE, "configs", cfg["builder"] + ".py")
    configure(enabled=True)
    for program in programs.split(","):
        built = builder.build(cfg, mix, mesh, kfac_on=program != "twin")
        state = jax.eval_shape(built["init_state"])
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        get_telemetry().reset()
        built["train_step"].trace(state, built["batch_struct"], scalar, scalar,
                                  **FLAGS.get(program, FLAGS["plain"]))
        print(json.dumps({"cell": name, "program": program, "gauges": get_telemetry().gauges}), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
