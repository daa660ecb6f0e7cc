"""Readings the limits of a cell's output check are set from (PERF.md,
section 2), all in one process on the chip, because set-up is long:

* ``program``: the timed path against the reference, one reading per seed
  (the lower readings);
* ``control_bf16``: the reference in bfloat16
  (``reference/kfac_sgd.py::Precision``) put in the program's place;
  ``control_own``: the program's own ``--bf16`` path, where it has one (the
  upper readings);
* ``highest``: the program traced under
  ``jax.default_matmul_precision("highest")``, a second witness for where
  the program's gap to the reference comes from;
* ``half_batch``: the program fed batches whose second half repeats the
  first: every mean over the batch (loss, gradients, batch statistics,
  covariances) is then the mean over the first half, which is the fault "half
  of the batch left out, the mean taken over the rest", with no new program.

    python benchmarks/tests/chip_readings.py <cell> --program 101,102 --control-own 101 --half-batch 101

Every reading goes through the harness's own comparison, ``check.decide``
with the cell's limits, and carries its ``correct``: a ``program`` reading
has to come out correct, a control or a fault not. One JSON line per reading is
written to ``chiprun_out/readings_<cell>.jsonl``; the lines the limits were
set from are kept under ``benchmarks/tests/readings/<cell>.jsonl``, and

    python benchmarks/tests/chip_readings.py <cell> --decide <file.jsonl>

decides recorded lines again under the limits the cell has now (no chip, no
JAX; ``tests/test_contract.py`` does so for every kept file). Exit 1 where a
reading comes out on the wrong side.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "examples"), HERE]

import run as bench  # noqa: E402


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def repeat_first_half(batch):
    import numpy as np

    return tuple(np.concatenate([a[: len(a) // 2]] * 2) for a in batch)


def decided(check, kind, values, limits):
    """``(correct, checks, as_expected)`` of one reading under ``limits``,
    those of them that a reading of three steps holds (no window: no count
    of compilations or of losses that are not finite). A ``program`` reading
    is expected correct, a control or a fault not; the ``highest`` witness
    may come out either way."""
    correct, checks = check.decide(values, {k: v for k, v in limits.items() if k in values})
    return correct, checks, kind == "highest" or correct == (kind == "program")


def decide_recorded(cell, path):
    """Recorded readings under the cell's limits as they are now."""
    check = bench.load_module(bench.HERE, "check.py")
    wrong = 0
    for text in open(path):
        row = json.loads(text)
        if row["cell"] != cell["name"]:
            continue
        correct, checks, ok = decided(check, row["kind"], row, cell["file"]["limits"])
        wrong += not ok
        over = {k: c["value"] for k, c in checks.items() if not c["value"] <= c["limit"]}
        print(f"{row['kind']:>13} seed {row['seed']}: correct {correct}"
              f"{'' if ok else '  <-- on the wrong side'}  over its limit: {over}")
    return wrong


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("cell")
    p.add_argument("--program", type=seeds, default=[])
    p.add_argument("--control-bf16", type=seeds, default=[])
    p.add_argument("--control-own", type=seeds, default=[])
    p.add_argument("--highest", type=seeds, default=[])
    p.add_argument("--half-batch", type=seeds, default=[])
    p.add_argument("--decide", default=None, help="a file of recorded readings to decide again; runs nothing")
    p.add_argument("--allow-cpu", action="store_true", help="rehearsal only: no device number comes of it")
    p.add_argument("--benchmark", default=None, help="a BENCHMARK.json other than the root's (tests)")
    a = p.parse_args(argv)

    if a.benchmark:
        cell = bench.load_cell(a.cell, benchmark=bench.load_json(a.benchmark),
                               base=os.path.dirname(os.path.abspath(a.benchmark)))
    else:
        cell = bench.load_cell(a.cell)
    if a.decide:
        sys.exit(1 if decide_recorded(cell, a.decide) else 0)
    bench.place_compile_cache()
    import jax

    devices = jax.devices()[: cell["chips"]] if a.allow_cpu else bench.find_devices(cell["chips"])[0]
    check = bench.load_module(bench.HERE, "check.py")
    traffic = bench.load_module(bench.HERE, "traffic.py")
    cfg, mix = cell["cfg"], dict(cell["traffic_mix"], pool=bench.CHECKED_STEPS)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"readings_{a.cell}.jsonl"), "a")

    wrong = 0

    def emit(kind, seed, values, where, t0):
        nonlocal wrong
        correct, checks, ok = decided(check, kind, values, cell["file"]["limits"])
        wrong += not ok
        row = {"cell": a.cell, "kind": kind, "seed": seed, "device": devices[0].device_kind,
               "seconds": round(time.time() - t0, 1), **values, "correct": correct,
               "as_expected": ok, "where": where, "checks": checks}
        print(json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()

    program = bench.Program(cell, devices)
    own_control = None
    refs = {}

    def reference(seed, pool, p0):
        if seed not in refs:
            refs[seed] = bench.run_reference(cell, p0, pool, float(program.lr))
        return refs[seed]

    import contextlib

    todo = (("program", a.program), ("half_batch", a.half_batch), ("control_bf16", a.control_bf16),
            ("highest", a.highest), ("control_own", a.control_own))
    for kind, seeds_of_kind in todo:
        for seed in seeds_of_kind:
            t0 = time.time()
            pool = traffic.make_pool(mix, cfg, len(devices), seed)
            if kind == "control_bf16":
                _, p0 = program.start(seed)
                got = bench.run_reference(cell, p0, pool, float(program.lr), precision="bfloat16")
            else:
                runner, context = program, contextlib.nullcontext()
                if kind == "control_own":
                    own_control = own_control or bench.Program(cell, devices, lower_precision=True)
                    runner = own_control
                elif kind == "highest":
                    context = jax.default_matmul_precision("highest")
                fed = [repeat_first_half(b) for b in pool] if kind == "half_batch" else pool
                with context:
                    state, p0 = runner.start(seed)
                    state, _, got = runner.first_steps(state, p0, traffic.feed(fed), bench.CHECKED_STEPS)
                del state
            values, where = check.readings(got, reference(seed, pool, p0))
            emit(kind, seed, values, where, t0)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
