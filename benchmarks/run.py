"""One benchmark cell, once, in one process, on the chip or not at all.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own, found by the name in
``BENCHMARK.json`` (PERF.md, section 4, says which). The last line on
standard output is the result, one JSON object.
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse
import importlib.util
import json
import math
import os
import resource
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECKED_STEPS = 3  # the reference follows the first three steps
PLAIN = {"update_factors": False, "update_eigen": False}  # the flags of a step with no K-FAC update


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(*parts)
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def process_start_time():
    """Wall-clock time at which this process was created (Linux), so that
    ``setup_s`` counts the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return min(boot + ticks / os.sysconf("SC_CLK_TCK"), _T_IMPORT)
    except (OSError, ValueError, StopIteration):
        return _T_IMPORT


def load_cell(name, benchmark=None, base=HERE):
    """The cell's entry in ``BENCHMARK.json`` with its files read in:
    configuration, traffic mix and the cell's own file (warm-up, limits)."""
    benchmark = benchmark or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        sys.exit(f"run.py: no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = dict(cells[name])
    config = next(c for c in benchmark["configs"] if c["name"] == cell["config"])
    cell["cfg"] = load_json(ROOT, config["file"])
    cell["traffic_mix"] = load_json(base, "traffic", cell["traffic"] + ".json")
    cell["file"] = load_json(base, "workloads", name + ".json")
    cell["benchmark"] = benchmark
    return cell


def find_devices(chips):
    """The cell's chips, or exit non-zero: no CPU fallback, and a device
    whose peaks are not tabled is an error, not a default."""
    import jax

    peaks = load_json(HERE, "peaks.json")["devices"]
    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if found["platform"] != "tpu" or found["kind"] not in peaks or found["count"] < chips:
        sys.exit(
            f"run.py: this cell needs {chips} TPU chip(s) of a kind listed in "
            f"benchmarks/peaks.json ({sorted(peaks)}); JAX found {found}"
        )
    return devices[:chips], peaks[found["kind"]]


CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def place_compile_cache():
    """JAX's persistent compilation cache at the fixed ``<checkout>/.jax_cache``,
    without a size limit, set through the environment before JAX is imported:
    JAX reads these variables itself, and the program's own
    ``compile_cache.enable_persistent_cache()``, which runs where the ImageNet
    trainer is imported (``examples/_env.py``) and nowhere on the LM cell's
    path, finds a directory given and sets no other. A directory given from
    outside is overridden on purpose: the cache has to lie inside the
    checkout, and the chip tool's own carried a size limit under which a
    cell's dozen programs (some 300 MiB) evicted each other, so that every
    process compiled again (PERF.md, Findings)."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"


def release_freed_host_memory():
    """Hand the heap's freed pages back to the system (glibc keeps them): a
    cold compile of the refresh program leaves the process at over 30 GiB of
    a one-chip machine's 40."""
    import ctypes

    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


class CompileClock:
    """What JAX reports about compilation (copied from chip_smoke.py): each
    backend compile or cache load with its seconds, and persistent-cache hits."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.durations, self.cache_hits = [], 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == self.COMPILE:
            self.durations.append(seconds)

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1


def step_kind(flags):
    return "refresh" if flags["update_eigen"] else "factors" if flags["update_factors"] else "plain"


def drive(step_fn, state, mesh, feed, flags_for, lr, damping, first_step,
          n_steps=None, seconds=None, period=1, hooks=None, static_flags=True):
    """The trainer's own loop (examples/train_imagenet_resnet.py:440-458):
    ``put_global_batch``, the jitted step with the host-known static flags,
    metrics fetched two steps behind so that the device stays fed. Returns
    the state and one record per step: the program it ran, the milliseconds
    between the host's receipt of the previous step's metrics and of this
    one's (the first: since the loop began), the loss, the put's milliseconds,
    and under ``counters`` every scalar the step's metrics hold (the loss
    too), fetched in the one ``device_get``.
    Ends after ``n_steps`` steps, or at the first whole ``period`` of steps
    once ``seconds`` have passed: a window holds whole periods of the K-FAC
    schedule, so that every run of a cell does the same work."""
    import jax
    from jax.profiler import TraceAnnotation

    from kfac_pytorch_tpu.parallel.mesh import put_global_batch

    records, pending = [], []
    t0 = last = time.perf_counter()

    def eat(item):
        nonlocal last
        step, kind, metrics, put_ms = item
        with TraceAnnotation("metric_fetch"):
            counters = {k: float(v) for k, v in jax.device_get(
                {k: v for k, v in metrics.items() if getattr(v, "ndim", None) == 0}).items()}
        now = time.perf_counter()
        records.append({"step": step, "kind": kind, "ms": (now - last) * 1e3,
                        "loss": counters["loss"], "put_ms": put_ms, "counters": counters})
        last = now

    step = first_step
    while True:
        if n_steps is not None and step - first_step >= n_steps:
            break
        if (seconds is not None and (step - first_step) % period == 0
                and time.perf_counter() - t0 >= seconds):
            break
        flags = flags_for(step)
        t_put = time.perf_counter()
        with TraceAnnotation("put_global_batch"):
            batch = put_global_batch(mesh, feed(step))
        put_ms = (time.perf_counter() - t_put) * 1e3
        with TraceAnnotation("dispatch"):
            state, metrics = step_fn(state, batch, lr, damping, **(flags if static_flags else {}))
        if hooks and step in hooks:
            hooks[step](state)
        pending.append((step, step_kind(flags), metrics, put_ms))
        step += 1
        if len(pending) > 2:
            eat(pending.pop(0))
    for item in pending:
        eat(item)
    jax.block_until_ready(state)
    return state, records, time.perf_counter() - t0


def metric_reader(name):
    """A per-layer metric's reader: ``metrics/<name>.py``, or, for a quantity
    split by the end-to-end metric it moves (``refresh_extra_ms.tail`` and
    ``.rare``), the one file of the part before the dot."""
    own = os.path.join(HERE, "metrics", name + ".py")
    return load_module(own if os.path.isfile(own) else os.path.join(HERE, "metrics", name.split(".")[0] + ".py"))


def percentile(values, q):
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def leaf_norms(names, vector):
    import jax

    return dict(zip(names, (float(v) for v in jax.device_get(vector))))


def norms_of_leaves(tree):
    """The Euclidean norm of every leaf, stacked in flattening order."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree_util.tree_leaves(tree)])


_REFERENCES = {}  # (cell, precision) -> reference_programs(): one process may follow many seeds


def reference_programs(cell, precision):
    """The reference's model and the jitted parts of its step for one cell
    (``reference/kfac_sgd.py::Steps``: the batch in the cell's row blocks,
    the K-FAC layers in its groups), and the leaves' norms."""
    import jax

    kf = load_module(HERE, "reference", "kfac_sgd.py")
    cfg, mix = cell["cfg"], cell["traffic_mix"]
    model = load_module(HERE, "reference", cfg["reference"] + ".py").Model(cfg, mix)
    hyper = kf.hyper_of(cfg)
    steps = kf.Steps(model, hyper, kf.Precision(precision),
                     row_blocks=cell["file"].get("reference_row_blocks", 1),
                     groups=cell["file"].get("reference_layer_groups", 1))
    return steps, jax.jit(norms_of_leaves)


def run_reference(cell, p0, pool, lr, steps=CHECKED_STEPS, precision="float32"):
    """The plain reference over the first ``steps`` steps from the weights
    ``p0``: ``{"loss", "grad1", "delta3"}`` as :func:`check.readings` takes
    them. ``precision`` ``"bfloat16"`` makes it a control."""
    import jax
    import jax.numpy as jnp

    weights = load_module(HERE, "weights.py")
    mix = cell["traffic_mix"]
    key = cell["name"], precision
    if key not in _REFERENCES:
        _REFERENCES[key] = reference_programs(cell, precision)
    reference, norms = _REFERENCES[key]
    names = [n for n, _ in weights.leaf_paths(p0)]
    state = reference.init(p0)
    out = {"loss": []}
    for k in range(steps):
        state, loss, grads, resid = reference.step(
            state, pool[k % len(pool)], jnp.float32(lr),
            capture=k % mix["fac_update_freq"] == 0, refresh=k % mix["kfac_update_freq"] == 0)
        if resid is not None:
            out["inverse_residual"] = max(out.get("inverse_residual", 0.0), float(resid))
        out["loss"].append(float(loss))
        if k == 0:
            out["grad1"] = leaf_norms(names, norms(grads))
        del grads
    delta = jax.tree_util.tree_map(lambda a, b: a - b, state.params, p0)
    out["delta3"] = leaf_norms(names, norms(delta))
    return out


class Program:
    """The system under test for one cell: the program's own jitted step on
    a mesh of the cell's chips, with what set-up needs round it (state and
    weights from the seed in one jitted call each, the two summaries the
    output check reads from the state). One object serves many seeds."""

    def __init__(self, cell, devices, *, lower_precision=False, break_step=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh
        from kfac_pytorch_tpu.training.step import kfac_flags_for_step

        weights = load_module(HERE, "weights.py")
        cfg, mix = cell["cfg"], cell["traffic_mix"]
        self.cell, self.chips = cell, len(devices)
        self.mesh = data_parallel_mesh(devices)
        self.replicated = NamedSharding(self.mesh, P())
        self.builder = load_module(HERE, "configs", cfg["builder"] + ".py")
        self.built = built = self.builder.build(cfg, mix, self.mesh, lower_precision=lower_precision)
        self.step_fn = break_step(built["train_step"]) if break_step else built["train_step"]
        self.lr = jnp.float32(cfg["base_lr"] * self.chips)
        self.damping = jnp.float32(cfg["kfac"]["damping"])
        self.flags_for = lambda step: kfac_flags_for_step(step, built["kfac"], built["epoch"])
        self.shapes = shapes = jax.eval_shape(built["init_state"])
        self.names = [n for n, _ in weights.leaf_paths(shapes.params)]
        rules = cfg["weights"]
        self.make_weights = jax.jit(
            lambda s: weights.make_weights(shapes.params, s, rules), out_shardings=self.replicated)
        self.make_state = jax.jit(
            lambda s: built["init_state"]().replace(params=weights.make_weights(shapes.params, s, rules)),
            out_shardings=self.replicated)
        wd, sub = cfg["weight_decay"], jax.tree_util.tree_map
        momentum = lambda opt_state: next(s.trace for s in opt_state if hasattr(s, "trace"))
        # after one step the momentum is g + wd p0: the gradient as the optimizer got it
        self.grad1_norms = jax.jit(lambda st, p0: norms_of_leaves(
            sub(lambda m, p: m - wd * p, momentum(st.opt_state), p0)))
        self.delta_norms = jax.jit(lambda st, p0: norms_of_leaves(
            sub(lambda p, q: p - q, st.params, p0)))

    def start(self, seed):
        """``(state, p0)``: the program's state and the benchmark's own copy
        of the starting weights, both made on the device from the seed."""
        seed32 = load_module(HERE, "weights.py").seed_scalar(seed)
        return self.make_state(seed32), self.make_weights(seed32)

    def first_steps(self, state, p0, feed, n_steps):
        """Drive the first ``n_steps`` (at least the checked three) of the
        schedule through the window's own loop. Returns the state, the step
        records and what the output check compares of the program."""
        seen = {}
        hooks = {
            0: lambda st: seen.__setitem__("grad1", self.grad1_norms(st, p0)),
            CHECKED_STEPS - 1: lambda st: seen.__setitem__("delta3", self.delta_norms(st, p0)),
        }
        state, records, _ = drive(self.step_fn, state, self.mesh, feed, self.flags_for, self.lr,
                                  self.damping, 0, n_steps=max(n_steps, CHECKED_STEPS), hooks=hooks)
        prog = {
            "loss": [r["loss"] for r in records[:CHECKED_STEPS]],
            "grad1": leaf_norms(self.names, seen["grad1"]),
            "delta3": leaf_norms(self.names, seen["delta3"]),
        }
        return state, records, prog


def run_cell(cell, seed, seconds, trace, devices, peak, *, break_step=None,
             log=sys.stderr, t_start=None):
    """Set up, warm up, measure for ``seconds``, check. Returns the result
    object. ``break_step`` wraps the jitted step: the tests plant faults there."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    t_start = t_start or time.time()
    clock = CompileClock()

    def stage(name):
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
        print(f"run.py: {name} at {time.time() - t_start:.1f} s, host peak {rss:.1f} GiB, "
              f"{len(clock.durations)} compiles or cache loads", file=log, flush=True)
    check = load_module(HERE, "check.py")
    weights = load_module(HERE, "weights.py")
    traffic = load_module(HERE, "traffic.py")
    cfg, mix, chips = cell["cfg"], cell["traffic_mix"], len(devices)
    samples_per_step = mix["per_chip_batch"] * chips

    # -- set-up: the program, its state and weights on the device from the seed
    program = Program(cell, devices, break_step=break_step)
    mesh, replicated, built, builder = program.mesh, program.replicated, program.built, program.builder
    step_fn, flags_for, lr, damping = program.step_fn, program.flags_for, program.lr, program.damping
    shapes, seed32 = program.shapes, weights.seed_scalar(seed)
    state, p0 = program.start(seed)
    pool = traffic.make_pool(mix, cfg, chips, seed)
    feed = traffic.feed(pool)

    # -- warm-up: the first steps of the schedule through the window's own
    # loop; they compile every program the window uses and are the steps
    # the reference follows
    warmup_steps = cell["file"]["warmup_steps"]
    state, warm, prog = program.first_steps(state, p0, feed, warmup_steps)
    kinds_warm = {r["kind"] for r in warm}
    stage("warm-up done")
    release_freed_host_memory()

    extras = {}
    if trace:
        # programs measured after the traced window are compiled here, as set-up
        batch_struct = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, P("data"))),
            built["batch_struct"])
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        with_sharding = lambda t: jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated), t)
        plain_flags = {**flags_for(1), **PLAIN}
        if "plain" not in kinds_warm:
            extras["plain"] = built["train_step"].lower(
                with_sharding(shapes), batch_struct, scalar, scalar, **plain_flags).compile()
        twin = builder.build(cfg, mix, mesh, kfac_on=False)
        twin_shapes = jax.eval_shape(twin["init_state"])
        extras["twin"] = twin["train_step"].lower(
            with_sharding(twin_shapes), batch_struct, scalar, scalar, **PLAIN).compile()
        extras["twin_state"] = jax.jit(
            lambda s: twin["init_state"]().replace(
                params=weights.make_weights(twin_shapes.params, s, cfg["weights"])),
            out_shardings=replicated).lower(seed32).compile()
    compiles_before = len(clock.durations)
    setup_s = time.time() - t_start

    # -- the measured window
    state, records, window_s = drive(step_fn, state, mesh, feed, flags_for, lr, damping,
                                     warmup_steps, seconds=seconds,
                                     period=mix["kfac_update_freq"])
    window_compiles = len(clock.durations) - compiles_before
    stage("window done")
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)

    # -- after the window (traced run): under the profiler, a short stretch of
    # its own (a trace of the whole window took the host past its 40 GiB):
    # steps of the kinds the cell's file lists, then a few of the plain
    # program where the schedule has none, then a few of the SGD twin. The
    # device time of each program run is read from the trace by its kind; the
    # plain program and the twin are also timed by the host's clock, outside
    # the profiler. The twin's state is made only once the program's is freed:
    # the two together did not fit beside a GPT-2 step's temporaries.
    trace_dir = os.path.join(HERE, ".trace")
    dispatched, plain_after, twin_records = [], [], []
    if trace:
        from jax.profiler import TraceAnnotation

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host's spans are TraceAnnotations
        # level 1 keeps them and drops the runtime's own fine events: at level 2
        # the host's transposition of each image batch alone wrote 5.6 million
        # events for 30 steps, a 424 MB trace (PERF.md, Findings)
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        kinds = cell["file"]["traced_kinds"]
        next_step = warmup_steps + len(records)
        n_few, n_after = cell["file"]["traced_extra_steps"], cell["file"]["after_window_steps"]
        flags_of = lambda kind: {**flags_for(1), "update_factors": kind != "plain",
                                 "update_eigen": kind == "refresh"}
        drive_extra = lambda program, st, flags, n: drive(
            program, st, mesh, feed, lambda s: flags, lr, damping, 0, n_steps=n, static_flags=False)
        if "plain" in extras:
            state, recs, _ = drive_extra(extras["plain"], state, plain_flags, n_after + 2)
            plain_after = [r["ms"] for r in recs[2:]]
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with TraceAnnotation("bench_window"):
            state, recs, _ = drive(step_fn, state, mesh, feed, lambda s: flags_of(kinds[s - next_step]),
                                   lr, damping, next_step, n_steps=len(kinds))
        dispatched += [r["kind"] for r in recs]
        if "plain" in extras:
            state, recs, _ = drive_extra(extras["plain"], state, plain_flags, n_few)
            dispatched += ["plain"] * len(recs)
        del state
        twin_state = extras["twin_state"](seed32)
        twin_state, recs, _ = drive_extra(extras["twin"], twin_state, PLAIN, n_few)
        dispatched += ["twin"] * len(recs)
        stage("traced steps done")
        jax.profiler.stop_trace()
        stage("trace written")
        twin_state, recs, _ = drive_extra(extras["twin"], twin_state, PLAIN, n_after + 2)
        twin_records = [r["ms"] for r in recs[2:]]
        del twin_state
    else:
        del state
    extras.clear()

    stage("program done")
    # -- the output check: the plain reference over the same first steps
    t_ref = time.time()
    ref = run_reference(cell, p0, pool, float(lr))
    ref_s = time.time() - t_ref
    stage("reference done")
    values, where = check.readings(prog, ref)
    values["window_compiles"] = float(window_compiles)
    failed = sum(1 for r in records if not math.isfinite(r["loss"]))
    values["nonfinite_losses"] = float(failed)
    correct, rows = check.decide(values, cell["file"]["limits"])

    # -- metrics
    step_ms = [r["ms"] for r in records]
    run = {
        "cell": cell["name"], "records": records, "window_s": window_s,
        "samples_per_step": samples_per_step, "chips": chips, "peak": peak,
        "plain_after_ms": plain_after, "twin_ms": twin_records, "trace": None, "device_ms": {},
        "cfg": cfg, "traffic_mix": mix,
    }
    end_to_end = {
        "samples_per_s": (len(records) * samples_per_step / window_s, "samples/s"),
        "step_p95_ms": (percentile(step_ms, 0.95), "ms"),
        "peak_hbm_gib": (peak_bytes / 2**30, "GiB"),
        "setup_s": (setup_s, "s"),
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(records), "failed": failed}
    bench = cell["benchmark"]
    if trace:
        reduce = load_module(HERE, "trace_reduce.py")
        run["trace"] = reduce.reduce_dir(trace_dir, chips)
        by_kind = reduce.seconds_by_kind(run["trace"]["module_runs"], dispatched)
        if not by_kind:
            print(f"run.py: the trace's program runs do not match the {len(dispatched)} steps "
                  "dispatched under it: no device time by kind", file=log)
        run["device_ms"] = {k: [sec * 1e3 for sec in v] for k, v in by_kind.items()}
        phases = load_module(HERE, "trace_phases.py")
        run["phase_ms"] = phases.by_kind(trace_dir, dispatched)
        run["phase_median_ms"] = lambda names, **how: phases.median_ms(run, names, **how)
        stage("trace reduced")
        shutil.rmtree(trace_dir, ignore_errors=True)
        common = load_module(HERE, "work", "common.py")
        run["work"] = load_module(HERE, "work", cfg["work"] + ".py").work(cfg, mix, chips)
        run["work"].update(common.kfac_work(run["work"]["layers"]))
        run["least_seconds"] = lambda w: common.least_seconds(w, peak, chips)
        metrics = {}

        def read(name):
            if name not in metrics:
                metrics[name] = metric_reader(name).read(run)
            return metrics[name]

        run["read"] = read
        result["metrics"] = {}
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            v = read(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {"device_ops": run["trace"]["top_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"],
                               "device_phases": phases.window_seconds(run["phase_ms"], cell["file"]["traced_kinds"])}
    else:
        result["metrics"] = {
            m["name"]: {"value": end_to_end[m["name"]][0], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]
        }
    result["device"] = device
    kinds = {}
    for r in records:
        kinds.setdefault(r["kind"], []).append(r["ms"])
    result["info"] = {
        "seed": seed, "window_s": window_s, "setup_s": setup_s, "reference_s": ref_s,
        "host_peak_rss_gib": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20,
        "compile_events": len(clock.durations), "compile_s": sum(clock.durations),
        "cache_hits": clock.cache_hits,
        "step_ms_median_by_kind": {k: statistics.median(v) for k, v in kinds.items()},
        "steps_by_kind": {k: len(v) for k, v in kinds.items()},
        "slowest_steps": [[r["step"], r["kind"], r["ms"]] for r in sorted(records, key=lambda r: -r["ms"])[:3]],
        "samples_per_s": end_to_end["samples_per_s"][0],
        "worst_leaves": where,
    }
    result["checks"] = rows  # last: each number compared, beside its limit
    for name, row in rows.items():
        print(f"check {name}: value {row['value']!r} limit {row['limit']!r}"
              + (f" ({where[name]['leaf']})" if name in where else ""), file=log)
    print(f"correct: {correct}", file=log)
    return result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    a = p.parse_args(argv)
    t_start = process_start_time()

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    place_compile_cache()
    cell = load_cell(a.workload)
    devices, peak = find_devices(cell["chips"])
    print(f"run.py: {a.workload} seed {a.seed} on {len(devices)} x {devices[0].device_kind} "
          f"({devices[0].platform}); compile cache {CACHE_DIR}", file=sys.stderr)
    result = run_cell(cell, a.seed, a.seconds, bool(a.trace), devices, peak, t_start=t_start)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
