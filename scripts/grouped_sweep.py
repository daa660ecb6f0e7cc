"""Grouped products on the chip: ops/grouped.py's two paths (XLA's
``ragged_dot`` and the Pallas ``gmm`` / ``tgmm`` kernels) at an expert bank's
shapes: results against float64 on the host (is the projection one bfloat16
pass, is the Gram float32 at highest?) and times by the host's clock round
``block_until_ready``, over tile sizes. Run by hand through the chip tool
(about two chip minutes); one JSON line per reading.

    python scripts/grouped_sweep.py [rows] [groups]
"""

import importlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kfac_pytorch_tpu.ops import grouped


def timed(fn, *args, reps=20):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e3


def main(rows=8192, groups=8):
    rows, groups = int(rows), int(groups)
    gmm, tgmm = grouped._kernels()
    rng = np.random.default_rng(0)
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform, "rows": rows, "groups": groups}))
    for load in (rows // 8, rows // 2, rows):  # rows that the groups fill
        sizes = np.full(groups, load // groups, np.int32)
        sizes[0] += load - sizes.sum()
        gs = jnp.asarray(sizes)
        for a, m in ((2048, 1536), (1536, 2048)):
            x = jnp.asarray(rng.standard_normal((rows, a)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((groups, a, m)) * 0.02, jnp.float32)
            lo = np.concatenate([[0], np.cumsum(sizes)])
            x64, w64 = np.asarray(x, np.float64), np.asarray(w, np.float64)
            want = np.concatenate([x64[lo[e]:lo[e + 1]] @ w64[e] for e in range(groups)])
            gap = lambda y: float(np.abs(np.asarray(y, np.float64)[:load] - want).max() / np.abs(want).max())
            fwd = {"ragged_dot": jax.jit(lambda x, w: lax.ragged_dot(x, w, gs))}
            for tiles in ((128, 512, 512), (128, 1024, 512), (256, 1024, 512), (512, 1024, 512)):
                fwd[f"gmm{tiles}"] = jax.jit(lambda x, w, t=tiles: gmm(x, w, gs, jnp.float32, t))
            for name, fn in fwd.items():
                y, ms = timed(fn, x, w)
                print(json.dumps({"op": "matmul", "path": name, "a": a, "m": m, "load": load, "ms": ms, "gap": gap(y)}), flush=True)
            both = lambda f: jax.jit(jax.grad(lambda x, w: jnp.sum(f(x, w) ** 2), (0, 1)))
            for name, fn in (("ragged_dot", both(lambda x, w: lax.ragged_dot(x, w, gs))),
                             ("ops.grouped", both(lambda x, w: grouped.grouped_matmul(x, w, gs)))):
                _, ms = timed(fn, x, w, reps=10)
                print(json.dumps({"op": "matmul fwd+bwd", "path": name, "a": a, "m": m, "load": load, "ms": ms}), flush=True)
        x = jnp.asarray(rng.standard_normal((rows, 2048)), jnp.float32)
        x64 = np.asarray(x, np.float64)
        want = np.stack([x64[lo[e]:lo[e + 1]].T @ x64[lo[e]:lo[e + 1]] for e in range(groups)])
        dims = lax.RaggedDotDimensionNumbers(dot_dimension_numbers=(((0,), (0,)), ((), ())),
                                             lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
        grams = {"ragged_dot_general": jax.jit(lambda x: lax.ragged_dot_general(
            x, x, gs, dims, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32))}

        def kernel(tiles, precision):
            def fn(x):
                with jax.default_matmul_precision(precision):
                    return tgmm(x.T, x, gs, jnp.float32, tiles)
            return jax.jit(fn)

        for tiles in ((128, 512, 512), (256, 512, 512), (512, 512, 512), (128, 256, 256)):
            grams[f"tgmm{tiles}"] = kernel(tiles, "highest")
        grams["tgmm(128, 512, 512) default precision"] = kernel((128, 512, 512), "default")
        for name, fn in grams.items():
            y, ms = timed(fn, x)
            gap = float(np.abs(np.asarray(y, np.float64) - want).max() / np.abs(want).max())
            print(json.dumps({"op": "gram", "path": name, "side": 2048, "load": load, "ms": ms, "gap": gap}), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
