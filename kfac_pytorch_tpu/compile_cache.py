"""Persistent XLA compilation cache setup + silent-recompile detection.

TPU eigh (QDWH) compiles slowly per distinct shape (minutes at n≥2048 —
see ops/eigh.py). Shape bucketing bounds the number of compiles; this module
makes them one-time per machine by pointing JAX's persistent compilation
cache at a stable directory. The reference never faced this: cuSOLVER/MAGMA
eigensolvers are shipped pre-compiled (kfac_preconditioner.py:252).

Call :func:`enable_persistent_cache` BEFORE the first jit execution (import
time is fine; the config flags only take effect at backend init).

:class:`RecompileMonitor` is the runtime complement: the K-FAC trainer
compiles a *known, bounded* set of step variants (plain / factors / eigen /
warmup combinations picked by host-side static flags), so any growth of a
jitted function's trace cache beyond that expectation is a silent recompile
— usually a weak-ref'd hparam object or a shape drifting — and each one can
cost 30s+. The monitor turns that into a telemetry counter
(``compile/retraces``) instead of an invisible stall.
"""

from __future__ import annotations

import os
from typing import Dict

_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Enable JAX's on-disk compilation cache; returns the cache directory.

    The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX reads it itself and no directory is set in code; where it is
    not, the cache lives at the fixed ``<checkout>/.jax_cache`` (the path is
    part of the cache key, so a directory that moves never hits).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache everything non-trivial: eigh buckets are the point, but full
    # train-step programs (30s+ compiles) benefit just as much.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def expected_step_variants(kfac, plan=None, autotune_candidates: int = 0) -> int:
    """Compile-budget for a K-FAC train step under the standard schedules.

    The single source of truth the trainers hand to
    :meth:`RecompileMonitor.watch`. The count is EXACT, not a per-lever
    worst-case sum: it replays the real host-side cadence
    (``scheduler.EigenRefreshCadence`` — the same object the trainers
    drive the step with) over enough steps to cover the schedule's full
    period and counts the distinct static-flag combinations it emits.
    Summing independent per-lever bounds over-reserved composed plans —
    e.g. ``eigh_chunks`` whose chunk offsets never coincide with a
    ``fac_update_freq`` step compile fewer factor+chunk twins than the
    old ``3 + 2K`` formula budgeted — and an inflated budget makes the
    recompile monitor blind to exactly that many real retraces.

    ``plan`` (a ``planner.Plan``) budgets a plan *before* constructing a
    KFAC with it: the cadence replays against ``kfac``'s schedule hparams
    with the plan's lever values overriding. ``autotune_candidates``
    reserves programs for warmup micro-autotuning: each non-winning
    candidate timed through the same jitted step may compile up to a
    plain and a capture program before being discarded.

    A nonzero ``diag_warmup`` replays both phases — warmup epochs, then
    post-warmup on the same cadence (the mid-run flip), plus a fresh
    warm-started cadence for the resume-from-checkpoint case where the
    monolithic bootstrap refresh compiles in its post-warmup form.

    The ``solver="rsvd"`` vs ``"eigh"`` choice does NOT change the count:
    the rank policy is a pure function of static factor shapes, so it
    swaps WHICH programs compile (truncated vs dense refresh, Woodbury
    vs dense apply), never how many the schedule produces.
    The same holds for the int8 wire: ``factor_comm_dtype="int8"`` swaps
    the flush program's merge body and adds no static flag, so it does not
    widen the budget (tests/test_wire_quant.py pins this).
    ``solver="streaming"`` CAN change it: the replay drives the cadence
    with no drift signal (re-orth at every boundary), and a run with a
    wired signal may additionally skip boundary re-orths — so every
    ``update_eigen`` variant is budgeted alongside its eigen-off twin
    (the fold-instead-of-re-orth program). Since streaming refuses
    chunks and swap-slip, the total still shrinks relative to a chunked
    schedule.
    """
    if kfac is None:
        return 1 + 2 * int(autotune_candidates)

    import math
    import types

    from kfac_pytorch_tpu.observability import telemetry as _telemetry
    from kfac_pytorch_tpu.scheduler import EigenRefreshCadence

    sim = kfac
    if plan is not None:
        comm = getattr(kfac, "factor_comm", None)
        multi = bool(comm is not None and comm.multi_device)
        sim = types.SimpleNamespace(
            hparams=kfac.hparams,
            diag_warmup=kfac.diag_warmup,
            eigh_chunks=int(plan.eigh_chunks),
            factor_comm=types.SimpleNamespace(
                defer=plan.factor_comm_freq > 1 and multi,
                comm_freq=int(plan.factor_comm_freq),
            ),
            solver=plan.solver,
            solver_rank=plan.solver_rank,
            staleness_budget=int(getattr(plan, "staleness_budget", 0)),
            staleness_signal=None,
            stream_drift_threshold=float(
                getattr(plan, "stream_drift_threshold", 0.05)
            ),
            stream_drift_signal=None,
            service_devices=int(getattr(plan, "service_devices", 0)),
        )

    hp = sim.hparams
    comm_freq = (
        sim.factor_comm.comm_freq if sim.factor_comm.defer else 1
    ) if getattr(sim, "factor_comm", None) is not None else 1
    # One full period of the flag schedule: eigen boundaries, factor
    # steps, and the deferred-flush phase all repeat within
    # lcm(kfac_freq, fac_freq·comm_freq); replay two periods past the
    # bootstrap so every steady-state combination appears. Capped — the
    # replay is host-side flag arithmetic only.
    period = math.lcm(
        int(hp.kfac_update_freq), int(hp.fac_update_freq) * int(comm_freq)
    )
    horizon = min(2 * period + int(hp.kfac_update_freq) + 1, 20000)

    variants = set()

    def replay(cadence, start, steps, epoch):
        for s in range(start, start + steps):
            flags = cadence.flags_for_step(s, epoch=epoch)
            key = tuple(sorted(flags.items()))
            variants.add(key)
        return start + steps

    # flags_for_step mirrors cadence gauges into telemetry; the replay is
    # a simulation, so keep it off the real gauges.
    tel = _telemetry.get_telemetry()
    prev_enabled = tel.enabled
    tel.enabled = False
    try:
        warm_epoch = sim.diag_warmup
        cadence = EigenRefreshCadence(sim)
        if sim.diag_warmup > 0:
            # warmup phase, then the in-place flip to post-warmup
            nxt = replay(cadence, 0, horizon, epoch=0)
            replay(cadence, nxt, horizon, epoch=warm_epoch)
            # resume case: fresh cadence already past warmup
            replay(EigenRefreshCadence(sim), 0, horizon, epoch=warm_epoch)
        else:
            replay(cadence, 0, horizon, epoch=warm_epoch)
    finally:
        tel.enabled = prev_enabled

    # Bounded-staleness slip variants. The replay above never slips: it
    # drives the cadence with no staleness signal (pressure 0), which is
    # also what a deterministic training run without a registered signal
    # does. A run WITH a signal can additionally emit, within each refresh
    # interval that has slack (chunked refresh shorter than
    # kfac_update_freq):
    #   - the withheld swap: the final-chunk step with ``swap_eigen``
    #     forced off (chunk eigh lands, double-buffer swap deferred), and
    #   - the bare-swap catch-up: any later chunk-free, non-refresh step
    #     with ``swap_eigen`` added to promote the pending buffer.
    # Flush slip reuses existing variants (a withheld due-flush is the
    # non-due capture program; the catch-up is the due-flush program), so
    # only the swap twins are budgeted. This is a deterministic superset
    # of what any pressure trace can produce.
    budget = int(getattr(sim, "staleness_budget", 0) or 0)
    k_eff = max(1, min(int(getattr(sim, "eigh_chunks", 1) or 1),
                       int(hp.kfac_update_freq)))
    if budget > 0 and k_eff > 1 and k_eff < int(hp.kfac_update_freq):
        extra = set()
        for key in variants:
            flags = dict(key)
            if flags.get("swap_eigen") and "eigen_chunk" in flags:
                twin = dict(flags)
                twin["swap_eigen"] = False
                extra.add(tuple(sorted(twin.items())))
            if (
                "eigen_chunk" not in flags
                and not flags.get("update_eigen")
                and not flags.get("swap_eigen")
            ):
                twin = dict(flags)
                twin["swap_eigen"] = True
                extra.add(tuple(sorted(twin.items())))
        variants |= extra

    # Streaming skipped-re-orth twins. The no-signal replay above
    # re-orthonormalizes at every boundary; a run with a wired drift
    # signal may instead skip a boundary — same step schedule, same
    # (forced) flush, but update_eigen off: the fold-only program. Budget
    # an eigen-off twin for every eigen-on variant so a quiet drift gauge
    # never reads as a retrace.
    if getattr(sim, "solver", "eigh") == "streaming":
        extra = set()
        for key in variants:
            flags = dict(key)
            if flags.get("update_eigen"):
                twin = dict(flags)
                twin["update_eigen"] = False
                extra.add(tuple(sorted(twin.items())))
        variants |= extra

    return len(variants) + 2 * int(autotune_candidates)


class RecompileMonitor:
    """Watch jitted functions for trace-cache growth beyond expectations.

    Register each jitted callable with the number of compiled variants the
    training schedule legitimately produces (e.g. a K-FAC step has up to 4:
    plain / factors-only / factors+eigen / warmup-diag). ``check()`` reads
    the function's trace-cache size (``_cache_size``, stable across the jax
    versions this repo pins); any count above the expectation increments
    the ``compile/retraces`` telemetry counter and is reported so the train
    loop can warn. Cheap enough to call once per epoch.
    """

    def __init__(self, telemetry=None):
        if telemetry is None:
            from kfac_pytorch_tpu.observability.telemetry import get_telemetry

            telemetry = get_telemetry()
        self._telemetry = telemetry
        self._watched: Dict[str, tuple] = {}
        self._reported: Dict[str, int] = {}

    def watch(self, name: str, fn, expected_variants: int = 1) -> None:
        """Track ``fn`` (a ``jax.jit`` result); ``expected_variants`` is the
        number of distinct compiled programs the schedule should create."""
        if not hasattr(fn, "_cache_size"):
            return  # not a jitted function (e.g. an eager fallback) — skip
        self._watched[name] = (fn, int(expected_variants))
        self._reported.setdefault(name, 0)

    def check(self) -> Dict[str, int]:
        """Return {name: excess_compile_count} for watched fns over budget.

        Each *new* excess compile since the last check bumps the
        ``compile/retraces`` counter once, and the per-function totals are
        mirrored into ``compile/cache_size/<name>``-style gauges so the
        Prometheus view shows absolute cache sizes too.
        """
        excess: Dict[str, int] = {}
        for name, (fn, budget) in self._watched.items():
            try:
                size = int(fn._cache_size())
            except Exception:
                continue
            self._telemetry.set_gauge(f"compile/cache_size/{name}", size)
            over = max(0, size - budget)
            new = over - self._reported[name]
            if new > 0:
                self._telemetry.inc("compile/retraces", new)
                self._reported[name] = over
            if over:
                excess[name] = over
        return excess
