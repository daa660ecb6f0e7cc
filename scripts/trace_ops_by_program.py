"""A traced benchmark run's device ops, per program run (chip tool only).

``breakdown.device_ops`` of a result line sums an op's name over every traced
program, and the compiler gives the same name to different ops in different
programs. This wraps ``benchmarks/run.py`` of the checkout in the cwd and,
before the traced run's trace directory is removed, writes for every program
run on the first device its device time, its fifteen longest ops and every op
whose whole HLO line (result and operand shapes included) matches a regex:

    python scripts/trace_ops_by_program.py <out.json> <regex> --workload <cell> --seed <n> --seconds 15 --trace 1

e.g. ``'\\b50257\\b'`` for everything that touches GPT-2's vocabulary (PERF.md,
section 6, PR 31). The result line is printed as ``run.py`` prints it.
"""

import glob
import json
import os
import re
import runpy
import shutil
import sys


def dump(trace_dir, out_path, pattern):
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return
    data = jax.profiler.ProfileData.from_file(files[0])
    plane = next(p for p in data.planes if re.match(r"/device:TPU:0$", p.name))
    lines = {line.name: line for line in plane.lines}
    events = lambda name: sorted((e.start_ns, e.start_ns + e.duration_ns, e.name) for e in lines[name].events)
    ops, runs = events("XLA Ops"), []
    for start, end, module in events("XLA Modules"):
        rows = [(n.split(" = ", 1)[0], (e - s) * 1e-6, n) for s, e, n in ops if s >= start and e <= end]
        runs.append({
            "module": module, "ms": (end - start) * 1e-6, "n_ops": len(rows),
            "matched": [(name, ms, text[:300]) for name, ms, text in rows if pattern.search(text)],
            "top": sorted(((name, ms) for name, ms, _ in rows), key=lambda r: -r[1])[:15],
        })
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(runs, f)


def main():
    out_path, pattern = sys.argv[1], re.compile(sys.argv[2])
    sys.argv = ["benchmarks/run.py"] + sys.argv[3:]
    rmtree = shutil.rmtree

    def dump_then_remove(path, *args, **kwargs):
        if os.path.basename(str(path).rstrip("/")) == ".trace" and os.path.isdir(path):
            try:
                dump(path, out_path, pattern)
            except Exception as e:  # noqa: BLE001 — the benchmark's result comes first
                print("trace_ops_by_program: no table:", repr(e), file=sys.stderr)
        return rmtree(path, *args, **kwargs)

    shutil.rmtree = dump_then_remove
    runpy.run_path("benchmarks/run.py", run_name="__main__")


if __name__ == "__main__":
    main()
