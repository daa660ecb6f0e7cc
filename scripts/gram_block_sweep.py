#!/usr/bin/env python
"""Time ops/factors.py::_gram on the chip over sides, block widths and row tiles.

The readings `_GRAM_MIN_SIDE`, `_GRAM_BLOCK` and `_GRAM_ROW_TILES` were chosen
from (root PERF.md, section 6, PR 26). For every `rows x side` operand it times
the one full product and the blocked form at each `block x tile`, compares the
two results, and reads how much device memory the loaded program keeps (its
code). Needs the TPU: a time from a CPU says nothing.

    python scripts/gram_block_sweep.py [--rows 8192] [--sides 768,3072] \
        [--blocks 256,512] [--tiles 1024,512] [--bias 0|1] [--reps 30]

Prints one JSON line per (rows, side, block, tile) and appends it to
chiprun_out/gram_block_sweep.jsonl.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kfac_pytorch_tpu.ops import factors  # noqa: E402


def _ints(text: str) -> list:
    return [int(t) for t in text.split(",") if t]


def _time_ms(fn, x, reps: int) -> float:
    jax.block_until_ready(fn(x))  # compile, warm
    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="8192")
    ap.add_argument("--sides", default="512,768,1024,1536,2304,3072,4608")
    ap.add_argument("--blocks", default="128,256,384,512")
    ap.add_argument("--tiles", default="1024,512")
    ap.add_argument("--bias", type=int, default=0)
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"gram_block_sweep needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    corner = 1.0 if args.bias else None
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    log = open(out / "gram_block_sweep.jsonl", "a")
    for n in _ints(args.rows):
        for d in _ints(args.sides):
            x = jax.random.normal(jax.random.PRNGKey(d), (n, d), jnp.float32)
            want = None
            blocked = [(b, t) for b in _ints(args.blocks) for t in _ints(args.tiles) if b < d]
            for tiles in [()] + blocked:
                fn = jax.jit(
                    lambda x, tiles=tiles: factors._gram(
                        x, (("div", n),), corner=corner, tiles=tiles
                    )
                )
                before = dev.memory_stats()["bytes_in_use"]
                ms = _time_ms(fn, x, args.reps)
                kept = dev.memory_stats()["bytes_in_use"] - before
                got = np.asarray(fn(x))
                if want is None:
                    want, full_ms = got, ms
                line = {
                    "device": dev.device_kind, "rows": n, "side": d, "bias": args.bias,
                    "block": tiles[0] if tiles else 0, "tile": tiles[1] if tiles else 0,
                    "ms": ms, "of_full": ms / full_ms, "program_bytes": kept,
                    "max_abs_diff": float(np.abs(got - want).max()),
                    "max_abs": float(np.abs(want).max()),
                    "symmetric": bool((got == got.T).all()),
                }
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
                log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
