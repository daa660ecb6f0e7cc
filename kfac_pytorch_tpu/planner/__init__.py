"""planner/ — cost-model-driven composition of the K-FAC perf levers.

One production entry point over the levers PRs 2–6 landed individually:

* :mod:`profiles` — the :class:`Plan` record, the declarative lever-
  composition validity matrix (every refusal path the levers introduced),
  and the named profile table;
* :mod:`cost_model` — analytic per-lever cost/benefit from layer shape
  buckets, the LPT slot-cost tables, mesh shape, and bytes-on-wire;
* :mod:`autotune` — optional warmup micro-autotune over 2–3 candidate
  plans;
* :mod:`drift` — post-run plan-vs-measured comparison publishing the
  ``kfac/plan_drift_*`` ratio gauges.

Consumed by ``KFAC(...)`` itself (preconditioner.py: the validity matrix
on every construction, the rest under ``profile=``), both example CLIs
(``--profile``/``--autotune-steps``) and the golden-plan lint
``scripts/check_plan_snapshot.py``. See docs/PLANNER.md.
"""

from kfac_pytorch_tpu.planner.autotune import (
    DEFAULT_AUTOTUNE_STEPS,
    AutotuneReport,
    autotune,
    candidate_plans,
)
from kfac_pytorch_tpu.planner.cost_model import (
    CostReport,
    ModelFacts,
    model_facts,
    plan_wire_bytes,
    resolve_profile,
)
from kfac_pytorch_tpu.planner.drift import (
    DriftReport,
    detect_drift,
    measured_wire_bytes_f32,
)
from kfac_pytorch_tpu.planner.profiles import (
    PROFILES,
    Plan,
    PlanEnv,
    Rule,
    RULES,
    check_plan,
    fit_plan,
    profile_names,
    violations,
)
from kfac_pytorch_tpu.observability.telemetry import get_telemetry

__all__ = [
    "AutotuneReport",
    "CostReport",
    "DEFAULT_AUTOTUNE_STEPS",
    "DriftReport",
    "ModelFacts",
    "PROFILES",
    "Plan",
    "PlanEnv",
    "RULES",
    "Rule",
    "autotune",
    "candidate_plans",
    "check_plan",
    "detect_drift",
    "fit_plan",
    "log_plan",
    "measured_wire_bytes_f32",
    "model_facts",
    "plan_wire_bytes",
    "profile_names",
    "resolve_profile",
    "violations",
]


def log_plan(plan: Plan, dropped=(), telemetry=None) -> None:
    """Publish a resolved plan as the structured ``kfac/plan_*`` gauge set.

    One numeric gauge per lever (booleans for the categorical ones), plus
    active/dropped counts — the registry rows live in
    docs/OBSERVABILITY.md and every name is a literal here so
    ``scripts/check_metric_names.py`` can hold both sides together.
    """
    tel = telemetry if telemetry is not None else get_telemetry()
    tel.set_gauge("kfac/plan_eigh_chunks", float(plan.eigh_chunks))
    tel.set_gauge(
        "kfac/plan_factor_kernel_pallas",
        1.0 if plan.factor_kernel == "pallas" else 0.0,
    )
    tel.set_gauge(
        "kfac/plan_factor_comm_bf16",
        1.0 if plan.factor_comm_dtype == "bf16" else 0.0,
    )
    tel.set_gauge(
        "kfac/plan_factor_comm_int8",
        1.0 if plan.factor_comm_dtype == "int8" else 0.0,
    )
    tel.set_gauge("kfac/plan_factor_comm_freq", float(plan.factor_comm_freq))
    tel.set_gauge(
        "kfac/plan_solver_rsvd", 1.0 if plan.solver == "rsvd" else 0.0
    )
    tel.set_gauge("kfac/plan_solver_rank", float(plan.solver_rank))
    tel.set_gauge(
        "kfac/plan_factor_sharding_owner",
        1.0 if plan.factor_sharding == "owner" else 0.0,
    )
    tel.set_gauge(
        "kfac/plan_levers_active", float(len(plan.non_default_levers()))
    )
    tel.set_gauge("kfac/plan_levers_dropped", float(len(dropped)))
