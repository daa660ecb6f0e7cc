#!/usr/bin/env python
"""Golden-plan lint: pin the cost model's resolved production plans.

The planner's decision thresholds (planner/cost_model.py) are plain
module constants, so an innocent-looking edit can silently flip which
levers `profile="production"` engages for every user. This lint resolves
the production profile for three canonical (model, mesh) fixtures and
diffs the full resolved plan + cost report against checked-in snapshots
in ``scripts/plan_snapshots/`` — cost-model drift becomes a visible
golden-file diff (reviewed and regenerated with ``--update``), not a
silent behavior change.

Fixtures are literal ``{layer: (g_side, a_side)}`` dicts captured from
the real models via ``planner.model_facts`` (see each fixture's note),
not live model inits — the lint must stay fast enough for tier-1 and
must not move when a model definition does (that drift should fail the
diff too, prompting a deliberate regeneration).

Wired into tests/test_scripts.py; exits 0 and prints OK when every
fixture matches.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SNAPSHOT_DIR = os.path.join(REPO, "scripts", "plan_snapshots")

# --- fixture 1: CIFAR-10 ResNet-32 on a v5e-8 (examples/train_cifar10_
# resnet.py's model, shapes = planner.model_facts over resnet32 init).
# All sides < 512: rsvd must NOT engage; the win is owner + wire levers.
_CIFAR_RESNET32 = {
    "BasicBlock_0/KFACConv_0": (16, 144), "BasicBlock_0/KFACConv_1": (16, 144),
    "BasicBlock_1/KFACConv_0": (16, 144), "BasicBlock_1/KFACConv_1": (16, 144),
    "BasicBlock_2/KFACConv_0": (16, 144), "BasicBlock_2/KFACConv_1": (16, 144),
    "BasicBlock_3/KFACConv_0": (16, 144), "BasicBlock_3/KFACConv_1": (16, 144),
    "BasicBlock_4/KFACConv_0": (16, 144), "BasicBlock_4/KFACConv_1": (16, 144),
    "BasicBlock_5/KFACConv_0": (32, 144), "BasicBlock_5/KFACConv_1": (32, 288),
    "BasicBlock_6/KFACConv_0": (32, 288), "BasicBlock_6/KFACConv_1": (32, 288),
    "BasicBlock_7/KFACConv_0": (32, 288), "BasicBlock_7/KFACConv_1": (32, 288),
    "BasicBlock_8/KFACConv_0": (32, 288), "BasicBlock_8/KFACConv_1": (32, 288),
    "BasicBlock_9/KFACConv_0": (32, 288), "BasicBlock_9/KFACConv_1": (32, 288),
    "BasicBlock_10/KFACConv_0": (64, 288), "BasicBlock_10/KFACConv_1": (64, 576),
    "BasicBlock_11/KFACConv_0": (64, 576), "BasicBlock_11/KFACConv_1": (64, 576),
    "BasicBlock_12/KFACConv_0": (64, 576), "BasicBlock_12/KFACConv_1": (64, 576),
    "BasicBlock_13/KFACConv_0": (64, 576), "BasicBlock_13/KFACConv_1": (64, 576),
    "BasicBlock_14/KFACConv_0": (64, 576), "BasicBlock_14/KFACConv_1": (64, 576),
    "KFACConv_0": (16, 27),
    "KFACDense_0": (10, 65),
}

# --- fixture 2: ImageNet ResNet-50 on a v5e-32 (shapes =
# planner.model_facts over resnet50 init). Big sides (4608, 2304, 2049...) → rsvd and the full lever stack should engage;
# the acceptance criterion (≥3 non-default levers) is pinned here.
_RESNET50 = {
    "Bottleneck_0/KFACConv_0": (64, 64), "Bottleneck_0/KFACConv_1": (64, 576),
    "Bottleneck_0/KFACConv_2": (256, 64), "Bottleneck_0/KFACConv_3": (256, 64),
    "Bottleneck_1/KFACConv_0": (64, 256), "Bottleneck_1/KFACConv_1": (64, 576),
    "Bottleneck_1/KFACConv_2": (256, 64),
    "Bottleneck_2/KFACConv_0": (64, 256), "Bottleneck_2/KFACConv_1": (64, 576),
    "Bottleneck_2/KFACConv_2": (256, 64),
    "Bottleneck_3/KFACConv_0": (128, 256), "Bottleneck_3/KFACConv_1": (128, 1152),
    "Bottleneck_3/KFACConv_2": (512, 128), "Bottleneck_3/KFACConv_3": (512, 256),
    "Bottleneck_4/KFACConv_0": (128, 512), "Bottleneck_4/KFACConv_1": (128, 1152),
    "Bottleneck_4/KFACConv_2": (512, 128),
    "Bottleneck_5/KFACConv_0": (128, 512), "Bottleneck_5/KFACConv_1": (128, 1152),
    "Bottleneck_5/KFACConv_2": (512, 128),
    "Bottleneck_6/KFACConv_0": (128, 512), "Bottleneck_6/KFACConv_1": (128, 1152),
    "Bottleneck_6/KFACConv_2": (512, 128),
    "Bottleneck_7/KFACConv_0": (256, 512), "Bottleneck_7/KFACConv_1": (256, 2304),
    "Bottleneck_7/KFACConv_2": (1024, 256), "Bottleneck_7/KFACConv_3": (1024, 512),
    "Bottleneck_8/KFACConv_0": (256, 1024), "Bottleneck_8/KFACConv_1": (256, 2304),
    "Bottleneck_8/KFACConv_2": (1024, 256),
    "Bottleneck_9/KFACConv_0": (256, 1024), "Bottleneck_9/KFACConv_1": (256, 2304),
    "Bottleneck_9/KFACConv_2": (1024, 256),
    "Bottleneck_10/KFACConv_0": (256, 1024), "Bottleneck_10/KFACConv_1": (256, 2304),
    "Bottleneck_10/KFACConv_2": (1024, 256),
    "Bottleneck_11/KFACConv_0": (256, 1024), "Bottleneck_11/KFACConv_1": (256, 2304),
    "Bottleneck_11/KFACConv_2": (1024, 256),
    "Bottleneck_12/KFACConv_0": (256, 1024), "Bottleneck_12/KFACConv_1": (256, 2304),
    "Bottleneck_12/KFACConv_2": (1024, 256),
    "Bottleneck_13/KFACConv_0": (512, 1024), "Bottleneck_13/KFACConv_1": (512, 4608),
    "Bottleneck_13/KFACConv_2": (2048, 512), "Bottleneck_13/KFACConv_3": (2048, 1024),
    "Bottleneck_14/KFACConv_0": (512, 2048), "Bottleneck_14/KFACConv_1": (512, 4608),
    "Bottleneck_14/KFACConv_2": (2048, 512),
    "Bottleneck_15/KFACConv_0": (512, 2048), "Bottleneck_15/KFACConv_1": (512, 4608),
    "Bottleneck_15/KFACConv_2": (2048, 512),
    "KFACConv_0": (64, 147),
    "KFACDense_0": (1000, 2049),
}

# --- fixture 3: transformer LM (vocab 32768, d_model 512, 4 blocks,
# kfac_embedding) on a v5e-8 pure-DP mesh (examples/train_transformer_
# lm.py's model at production size, shapes = planner.model_facts with
# capture.discover_layers). The diag-A embedding now COMPOSES with owner
# sharding (its [vocab] diagonal lays out as v-group vector slots,
# parallel/assignment.py) — the snapshot pins owner staying ON with the
# embedding in the shard report, where PR-6's matrix refused it.
_TRANSFORMER_LM = {
    **{
        f"block_{i}/{lay}": shape
        for i in range(4)
        for lay, shape in (
            ("qkv", (1536, 513)),
            ("out", (512, 513)),
            ("ff1", (2048, 513)),
            ("ff2", (512, 2049)),
        )
    },
    "decoder": (32768, 513),
    "tok_embed": (512, 32768),
}

# --- fixture 6: the LM on a v5e-32 3-D data×fsdp×tensor mesh (8 × 2 × 2,
# parallel/mesh.py::data_fsdp_tensor_mesh) with the MLP genuinely
# Megatron-split (--fsdp 2 --tensor-parallel 2): ff1 column-shards
# ("#c2", per-block G side 1024), ff2 row-shards ("#r2", per-block
# bias-free A side 1024). Shapes hold the PER-BLOCK sides; shard_counts
# carries (form, T). The snapshot pins the shard-lens exclusions firing
# by name (owner/chunks/streaming refused for the run, not silently),
# the surviving wire levers, and owner sizing to the BATCH world
# data×fsdp = 16, not the 32-device total.
_TRANSFORMER_LM_SHARDED = {
    **{
        f"block_{i}/{lay}": shape
        for i in range(4)
        for lay, shape in (
            ("qkv", (1536, 513)),
            ("out", (512, 513)),
            ("ff1#c2", (1024, 513)),
            ("ff2#r2", (512, 1024)),
        )
    },
    "decoder": (32768, 513),
    "tok_embed": (512, 32768),
}

_TRANSFORMER_LM_SHARD_COUNTS = {
    **{f"block_{i}/ff1#c2": ("c", 2) for i in range(4)},
    **{f"block_{i}/ff2#r2": ("r", 2) for i in range(4)},
}

FIXTURES = {
    "cifar_resnet32_x8": dict(
        shapes=_CIFAR_RESNET32,
        diag_a=(),
        has_conv=True,
        world=8,
        mesh_axes=("data",),
    ),
    "resnet50_x32": dict(
        shapes=_RESNET50,
        diag_a=(),
        has_conv=True,
        world=32,
        mesh_axes=("data",),
    ),
    "transformer_lm_x8": dict(
        shapes=_TRANSFORMER_LM,
        diag_a=("tok_embed",),
        has_conv=False,
        world=8,
        mesh_axes=("data",),
    ),
    # fixture 4: the same LM on a v5e-16 2-D data×tensor mesh (8 data × 2
    # tensor, parallel/mesh.py::data_tensor_mesh). The tensor axis carries
    # replicated compute, so the planner must treat the mesh as pure-DP
    # (no comm/owner/overlap drops) while sizing owner shards to the DATA
    # world (8), not the 16-device total.
    "transformer_lm_x8x2": dict(
        shapes=_TRANSFORMER_LM,
        diag_a=("tok_embed",),
        has_conv=False,
        world=16,
        data_world=8,
        mesh_axes=("data", "tensor"),
    ),
    # fixture 5: ResNet-50 again, but the operator offers a 2-device
    # curvature carve (--service-devices 2) under an aggressive refresh
    # cadence (K=10). The dense refresh per interval (5.0e11 MACs) clears
    # the engagement bar (3 · 2/32 · 10 · precond ≈ 1.5e11), so the cost
    # model moves the refresh off-step: service_devices=2 +
    # staleness_budget=1, solver back to dense eigh, chunks 1, REPLICATED
    # factors (service_vs_owner_sharding), wire/overlap levers intact. At
    # the default K=100 the same offer is declined (refresh amortizes
    # below the carved devices' capture loss) — fixture 2 pins that side.
    "transformer_lm_x8x2x2": dict(
        shapes=_TRANSFORMER_LM_SHARDED,
        shard_counts=_TRANSFORMER_LM_SHARD_COUNTS,
        diag_a=("tok_embed",),
        has_conv=False,
        world=32,
        data_world=16,
        mesh_axes=("data", "fsdp", "tensor"),
    ),
    "resnet50_x32_service": dict(
        shapes=_RESNET50,
        diag_a=(),
        has_conv=True,
        world=32,
        mesh_axes=("data",),
        service_devices=2,
        fac_update_freq=1,
        kfac_update_freq=10,
    ),
}


def resolve_fixture(name: str) -> dict:
    from kfac_pytorch_tpu.planner import ModelFacts, PlanEnv, resolve_profile

    fx = FIXTURES[name]
    facts = ModelFacts(
        shapes={k: tuple(v) for k, v in fx["shapes"].items()},
        diag_a=frozenset(fx["diag_a"]),
        has_conv=fx["has_conv"],
        shard_counts={
            k: (f, int(c)) for k, (f, c) in fx.get("shard_counts", {}).items()
        },
    )
    env = PlanEnv(
        world=fx["world"],
        data_world=fx.get("data_world", 0),
        mesh_axes=tuple(fx["mesh_axes"]),
        has_diag_a_layers=facts.has_diag_a,
        has_conv_layers=facts.has_conv,
        has_shard_lens_layers=facts.has_shard_lens,
        has_moe_layers=facts.has_moe,
        fac_update_freq=fx.get("fac_update_freq", 10),
        kfac_update_freq=fx.get("kfac_update_freq", 100),
        service_devices=fx.get("service_devices", 0),
    )
    plan, report, dropped = resolve_profile("production", facts, env)
    return {
        "fixture": name,
        "profile": "production",
        "plan": plan.to_dict(),
        "non_default_levers": list(plan.non_default_levers()),
        "dropped_rules": list(dropped),
        "cost": report.to_dict(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--update",
        action="store_true",
        help="regenerate the golden snapshots instead of diffing",
    )
    args = ap.parse_args(argv)

    os.makedirs(SNAPSHOT_DIR, exist_ok=True)
    failures = []
    for name in sorted(FIXTURES):
        resolved = resolve_fixture(name)
        path = os.path.join(SNAPSHOT_DIR, f"{name}.json")
        if args.update:
            with open(path, "w") as f:
                json.dump(resolved, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote {os.path.relpath(path, REPO)}")
            continue
        if not os.path.exists(path):
            failures.append(f"{name}: missing golden {path} (run --update)")
            continue
        with open(path) as f:
            golden = json.load(f)
        if golden != json.loads(json.dumps(resolved)):
            for key in sorted(set(golden) | set(resolved)):
                g, r = golden.get(key), json.loads(json.dumps(resolved)).get(key)
                if g != r:
                    failures.append(
                        f"{name}.{key}:\n  golden:   {g}\n  resolved: {r}"
                    )
    if args.update:
        return 0
    if failures:
        print("plan snapshot drift (review, then scripts/check_plan_snapshot.py --update):",
              file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"OK: {len(FIXTURES)} production plans match their goldens")
    return 0


if __name__ == "__main__":
    sys.exit(main())
