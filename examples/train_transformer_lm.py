"""Transformer LM training with distributed K-FAC + sequence parallelism.

The long-context application: a decoder-only transformer whose dense
projections train under the same distributed K-FAC preconditioner as the CNN
examples, with attention either replicated (``--seq-parallel 1``) or sharded
over a ``seq`` mesh axis via ring attention / Ulysses all-to-all
(``--seq-parallel N --attention ring|ulysses``, parallel/context.py). The
device mesh is data×seq; batch shards over ``data``, sequence over ``seq``.
Alternatively ``--tensor-parallel N`` builds the 2-D data×tensor mesh
(parallel/mesh.py): compute replicates over ``tensor`` while every K-FAC
collective rides the ``data`` axis, so the owner/comm/overlap levers all
stay available.

Synthetic smoke:
    python examples/train_transformer_lm.py --synthetic --epochs 1 \
        --steps-per-epoch 20 --seq-parallel 4 --attention ring
WikiText (word-level, wiki.train.tokens layout):
    python examples/train_transformer_lm.py --data-dir /path/to/wikitext-2
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import _env  # noqa: F401  (platform forcing — must precede jax use)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu import (
    KFAC,
    EigenRefreshCadence,
    KFACParamScheduler,
    capture,
    observability,
)
from kfac_pytorch_tpu.compile_cache import (
    RecompileMonitor,
    expected_step_variants,
)
from kfac_pytorch_tpu.models import glm_moe_lite, transformer_lm
from kfac_pytorch_tpu.parallel import launch
from kfac_pytorch_tpu.parallel.context import make_context_parallel_attention
from kfac_pytorch_tpu.parallel.mesh import put_sharded_batch
from kfac_pytorch_tpu.training import checkpoint as ckpt
from kfac_pytorch_tpu.training import data as data_lib
from kfac_pytorch_tpu.training import profiling
from kfac_pytorch_tpu.training.metrics import Metric, ScalarWriter
from kfac_pytorch_tpu.training.step import (
    TrainState,
    make_eval_step,
    make_sgd,
    make_train_step,
)


MODELS = ("transformer", "glm_moe_lite")


def build(model_name, sizes, *, global_batch, seq_len, attention_fn,
          momentum=0.9, weight_decay=1e-5, grad_clip=0.25, remat=False,
          kfac_kwargs=None, step_kwargs=None):
    """The LM trainer's objects for one model, as ``main`` wires them (and as
    the benchmark's builder holds them: benchmarks/configs/moe_lm.py).

    ``model_name`` is one of :data:`MODELS`; ``sizes`` the keyword arguments
    of its ``get_model`` (``transformer``: ``vocab_size``, ``d_model``,
    ``n_heads``, ``n_layers``, ...; ``glm_moe_lite``: the fields of
    ``models/glm_moe_lite.py::GLMMoELiteConfig``). ``kfac_kwargs`` are
    ``KFAC``'s (schedule, damping, mesh, ``profile``, ...; ``None`` trains by
    plain SGD); the layer list, a profile's factor shapes and, for a model
    with shared inputs, ``shared_a`` are derived here. ``step_kwargs`` go to ``make_train_step`` beside the
    gradient clip and the declared SGD.

    Returns ``model``, ``init_toks``, ``layers``, ``tx``, ``make_kfac``
    (``**overrides -> KFAC``, for the autotuner), ``kfac``, ``make_step``
    (``kfac -> step``), ``train_step`` and ``init_state(seed) -> TrainState``
    (not yet placed on a mesh)."""
    if model_name == "transformer":
        model = transformer_lm.get_model(
            max_len=seq_len, attention_fn=attention_fn, remat=remat, **sizes)
    elif model_name == "glm_moe_lite":
        model = glm_moe_lite.get_model(attention_fn=attention_fn, remat=remat, **sizes)
    else:
        raise ValueError(f"unknown model {model_name!r}: one of {MODELS}")
    init_toks = jnp.zeros((global_batch, seq_len), jnp.int32)
    tx = make_sgd(momentum=momentum, weight_decay=weight_decay)
    layers, make_kfac, kfac = None, None, None
    if kfac_kwargs is not None:
        layers = capture.discover_layers(model, init_toks, train=True)
        shared = glm_moe_lite.shared_inputs(layers) if model_name == "glm_moe_lite" else {}

        def make_kfac(**overrides):
            kwargs = {**kfac_kwargs, **overrides}
            if kwargs.get("profile"):
                # factor shapes for the planner's cost model (the discovered
                # layer list includes --kfac-embedding's diag-A entry)
                from kfac_pytorch_tpu import planner

                shapes = jax.eval_shape(
                    lambda: model.init(jax.random.PRNGKey(0), init_toks, train=True))
                kwargs["profile_shapes"] = planner.model_facts(shapes["params"], layers=layers)
            return KFAC(layers=layers, **({"shared_a": shared} if shared else {}), **kwargs)

        kfac = make_kfac()

    def make_step(kfac):
        return make_train_step(
            model, tx, kfac, train_kwargs={"train": True}, grad_clip=grad_clip,
            **(step_kwargs or {}),
        )

    def init_state(seed, kfac=kfac):
        params = model.init(jax.random.PRNGKey(seed), init_toks, train=True)["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
            opt_state=tx.init(params),
            kfac_state=kfac.init(params) if kfac else None,
        )

    return {
        "model": model, "init_toks": init_toks, "layers": layers, "tx": tx,
        "make_kfac": make_kfac, "kfac": kfac, "make_step": make_step,
        "train_step": make_step(kfac), "init_state": init_state,
    }


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="Transformer-LM K-FAC Example (TPU/JAX)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--data-dir", default=None, help="WikiText token dir")
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--log-dir", default="./logs")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--preempt-save-dir", default=None,
                   help="elastic snapshot dir: SIGTERM takes an emergency "
                        "snapshot and a restart scan-resumes the newest one "
                        "(docs/ELASTIC.md)")
    p.add_argument("--snapshot-every", type=int, default=0,
                   help="elastic: also snapshot every N steps "
                        "(needs --preempt-save-dir; 0 = emergency-only)")
    p.add_argument("--model", choices=MODELS, default="transformer",
                   help="transformer: models/transformer_lm.py from the flags "
                        "below; glm_moe_lite: models/glm_moe_lite.py from "
                        "--model-config")
    p.add_argument("--model-config", default=None,
                   help="JSON file holding the fields of GLMMoELiteConfig "
                        "(--model glm_moe_lite; vocab_size is the corpus's)")
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=128, help="tokens per sample")
    p.add_argument("--batch-size", type=int, default=8, help="per data-mesh-slot")
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--base-lr", type=float, default=0.01)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=1e-5)
    p.add_argument("--grad-clip", type=float, default=0.25)
    # parallelism: seq-parallel devices; remaining devices form the data axis
    p.add_argument("--seq-parallel", type=int, default=1,
                   help="devices on the 'seq' mesh axis (1 = no sequence parallelism)")
    p.add_argument("--tensor-parallel", type=int, default=1,
                   help="devices on the 'tensor' axis of a 2-D data×tensor "
                        "mesh (parallel/mesh.py data_tensor_mesh): params "
                        "and compute replicate over it while every K-FAC "
                        "collective — factor buckets, owner reduce-scatter, "
                        "the preconditioned-grad allgather — rides the "
                        "'data' axis only; incompatible with --seq-parallel")
    p.add_argument("--fsdp", type=int, default=0,
                   help="engage the sharded-parameter regime over the 3-D "
                        "data×fsdp×tensor mesh (parallel/mesh.py "
                        "data_fsdp_tensor_mesh): params shard over 'fsdp' "
                        "(leading-dim FSDP split) and — when "
                        "--tensor-parallel > 1 — the MLP kernels GENUINELY "
                        "shard over 'tensor' (Megatron column/row split, "
                        "per-shard K-FAC blocks; docs/SHARDING.md). 0 keeps "
                        "the legacy replicated-compute meshes; >= 1 is the "
                        "'fsdp' axis size (1 = tensor-sharding only)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="replace each block's dense MLP with a toy top-1 "
                        "MoE bank of this many experts (models/layers.py "
                        "KFACMoE): per-expert A/G factors with token-count-"
                        "weighted EMAs; 0 keeps the dense MLP; mutually "
                        "exclusive with a genuine tensor-parallel MLP")
    p.add_argument("--attention", choices=["ring", "ulysses"], default="ring")
    # K-FAC (same surface as the CNN trainers)
    p.add_argument("--remat", action="store_true",
                   help="rematerialize each transformer block in backward "
                        "(jax.checkpoint): activation memory O(1) in depth, "
                        "per-block recompute — the HBM lever for long "
                        "sequences on TPU")
    p.add_argument("--kfac-embedding", action="store_true",
                   help="precondition the token embedding too (diagonal-A "
                        "K-FAC; beyond the reference's Linear/Conv2d set); "
                        "capture streams token counts in O(B*T) via the "
                        "Pallas token-gather kernel on TPU (ops/"
                        "factor_kernels.py) — no [B*T,V] one-hot ever exists")
    p.add_argument("--qkv-lens", action="store_true",
                   help="expand-lens on each block's fused QKV projection: "
                        "three d_model-side G factors for the Q/K/V column "
                        "slices instead of one 3*d_model-side factor — ~9x "
                        "lighter refresh, bitwise-equal to an unfused "
                        "three-layer projection (models/transformer_lm.py)")
    p.add_argument("--tie-embeddings", action="store_true",
                   help="decoder head reuses the token-embedding table "
                        "(logits = x @ W.T); with --kfac-embedding the tied "
                        "table accumulates ONE set of K-FAC statistics over "
                        "both use sites (reduce lens)")
    p.add_argument("--kfac-update-freq", type=int, default=10, help="0 disables K-FAC")
    p.add_argument("--eigh-chunks", type=int, default=1,
                   help="pipeline the eigen refresh over this many steps "
                        "after each --kfac-update-freq boundary (double-"
                        "buffered basis, swapped when all chunks land); 1 = "
                        "monolithic refresh, bit-exact with prior releases "
                        "(docs/PERF.md)")
    p.add_argument("--kfac-cov-update-freq", type=int, default=1)
    p.add_argument("--stat-decay", type=float, default=0.95)
    p.add_argument("--damping", type=float, default=0.003)
    p.add_argument("--damping-alpha", type=float, default=0.5)
    p.add_argument("--damping-schedule", nargs="+", type=int, default=None)
    p.add_argument("--kl-clip", type=float, default=0.001)
    p.add_argument("--grad-comm-dtype", default=None, choices=[None, "bf16"],
                   help="downcast the per-step data-parallel gradient mean "
                        "on the wire (the reference's --fp16-allreduce on "
                        "DistributedOptimizer); pure-DP only "
                        "(--seq-parallel 1)")
    p.add_argument("--factor-comm-dtype", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="wire dtype of the bucketed K-FAC factor-statistics "
                        "exchange (parallel/comm.py); pure-DP only "
                        "(--seq-parallel 1); f32 = bitwise parity with the "
                        "per-layer exchange; int8 = block-scaled codes + "
                        "error feedback at 0.51x the bf16 bytes (requires "
                        "--factor-comm-freq > 1; docs/PERF.md 'Sub-bf16 "
                        "wire')")
    p.add_argument("--factor-comm-freq", type=int, default=1,
                   help="allreduce factor statistics every N capture steps "
                        "(merged running averages, always flushed before an "
                        "eigen refresh); pure-DP only; 1 = per-step, exact")
    p.add_argument("--factor-sharding", default="replicated",
                   choices=["replicated", "owner"],
                   help="owner: DP-KFAC owner-sharded curvature — factor "
                        "stats reduce-scatter onto each layer's eigen-owner "
                        "and ONE allgather replicates the preconditioned "
                        "grads; O(model/devices) factor memory and wire "
                        "(docs/PERF.md); needs a single data axis "
                        "(--seq-parallel 1; --tensor-parallel composes). "
                        "Diagonal-A embedding factors shard as [vocab] "
                        "vector slots, so --kfac-embedding composes too")
    p.add_argument("--solver", default="eigh",
                   choices=["eigh", "rsvd", "streaming"],
                   help="curvature eigensolver: eigh = full (dense) "
                        "eigendecomposition, rsvd = randomized truncated "
                        "eigensolve + low-rank Woodbury apply for factor "
                        "sides >= --solver-auto-threshold (docs/PERF.md)")
    p.add_argument("--solver-rank", type=int, default=128,
                   help="eigenpairs kept per truncated factor side "
                        "(--solver rsvd); watch kfac/spectrum_mass_captured "
                        "to size it")
    p.add_argument("--solver-auto-threshold", type=int, default=512,
                   help="factor sides at least this large use the truncated "
                        "solver; smaller sides stay dense (--solver rsvd)")
    p.add_argument("--stream-drift-threshold", type=float, default=0.05,
                   help="--solver streaming: re-orthonormalize at a refresh "
                        "boundary only when the residual-mass drift gauge "
                        "exceeds this (0 = every boundary, periodic rsvd)")
    p.add_argument("--comm-overlap", action="store_true",
                   help="fuse the factor-statistics reduction into the "
                        "gradient stream: the bucketed factor psums issue "
                        "before the gradient pmean so the collectives "
                        "interleave with backprop instead of queuing after "
                        "it (pure data-parallel multi-device mesh only; "
                        "bitwise-identical numerics; docs/PERF.md)")
    p.add_argument("--staleness-budget", type=int, default=0,
                   help="let a deferred factor flush or a completed pending "
                        "eigen swap slip up to this many steps under "
                        "measured comm/compute pressure (needs "
                        "--factor-comm-freq > 1, --eigh-chunks > 1 or "
                        "--service-devices > 0; 0 = never slip; watch the "
                        "kfac/staleness_* gauges)")
    p.add_argument("--service-devices", type=int, default=0,
                   help="carve this many devices out of the pure-DP mesh as "
                        "dedicated curvature workers (kfac_pytorch_tpu/"
                        "service/): the eigen refresh leaves the training "
                        "step; bases install between steps at bounded "
                        "staleness (docs/SERVICE.md); 0 = inline refresh")
    p.add_argument("--profile", default=None,
                   choices=["safe", "memory", "production"],
                   help="resolve the K-FAC perf levers from a named planner "
                        "profile (planner/cost_model.py) using this model's "
                        "factor shapes and the mesh; explicit lever flags "
                        "win over the profile's choices (docs/PLANNER.md)")
    p.add_argument("--autotune-steps", type=int, default=0,
                   help="time the resolved plan against its conservative "
                        "fallbacks for this many warmup steps each and pin "
                        "the winner (0 = trust the cost model; needs "
                        "--profile; docs/PLANNER.md)")
    p.add_argument("--profile-epoch", type=int, default=None,
                   help="capture a jax.profiler trace of this epoch into --log-dir")
    p.add_argument("--telemetry-dir", default=None,
                   help="enable structured telemetry and write metrics.prom "
                        "(Prometheus textfile) + telemetry.jsonl there each "
                        "epoch (docs/OBSERVABILITY.md)")
    p.add_argument("--kfac-diagnostics", action="store_true",
                   help="log per-epoch K-FAC stability telemetry (KL-clip "
                        "nu, damped eigenvalue range, condition numbers, "
                        "update/grad geometry) to --log-dir")
    p.add_argument("--seed", type=int, default=42)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)

    # enable BEFORE any spans fire (launch.initialize below has comm spans);
    # with the overlap plane on, span barriers are dropped — a
    # block_until_ready between dispatches would serialize the very
    # collectives the overlap interleaves
    tel = observability.configure(
        enabled=bool(args.telemetry_dir),
        block_spans=False if args.comm_overlap else None,
    )

    launch.initialize()
    devices = np.asarray(jax.devices())
    sp = args.seq_parallel
    tp = args.tensor_parallel
    fsdp = max(0, args.fsdp)
    # --fsdp >= 1 flips --tensor-parallel's meaning from "replicated-compute
    # second axis" (legacy 2-D data×tensor mesh) to GENUINE shard-lens
    # tensor parallelism over the 3-D mesh (kfac_pytorch_tpu/shardwise/)
    shardwise_regime = fsdp >= 1
    if sp > 1 and tp > 1:
        raise SystemExit(
            "--seq-parallel and --tensor-parallel are separate second mesh "
            "axes; pick one"
        )
    if shardwise_regime and sp > 1:
        raise SystemExit(
            "--fsdp builds the 3-D data×fsdp×tensor mesh; it does not "
            "compose with --seq-parallel"
        )
    if devices.size % sp != 0:
        raise SystemExit(f"--seq-parallel {sp} must divide device count {devices.size}")
    if devices.size % max(1, tp) != 0:
        raise SystemExit(
            f"--tensor-parallel {tp} must divide device count {devices.size}"
        )
    if shardwise_regime and devices.size % (fsdp * max(1, tp)) != 0:
        raise SystemExit(
            f"--fsdp {fsdp} x --tensor-parallel {tp} must divide device "
            f"count {devices.size}"
        )
    if args.moe_experts > 0 and shardwise_regime and tp > 1:
        raise SystemExit(
            "--moe-experts replaces the MLP that a genuine --tensor-parallel "
            "split (--fsdp >= 1) would shard; pick one"
        )
    if args.seq_len % sp != 0:
        raise SystemExit(f"--seq-len {args.seq_len} must be divisible by --seq-parallel {sp}")
    # CLI lever composition routed through the planner's validity matrix —
    # the same Rule rows KFAC.__init__/init enforce produce the refusal
    # messages here (owner×seq-parallel and factor-comm×seq-parallel were
    # ad-hoc SystemExits before PLANNER). A 'tensor' axis is exempt: the
    # matrix's pure_dp predicate knows K-FAC collectives still ride one
    # data axis through it.
    from kfac_pytorch_tpu import planner

    cli_plan = planner.Plan(
        eigh_chunks=args.eigh_chunks,
        factor_comm_dtype=args.factor_comm_dtype,
        factor_comm_freq=args.factor_comm_freq,
        solver=args.solver,
        solver_rank=args.solver_rank,
        solver_auto_threshold=args.solver_auto_threshold,
        stream_drift_threshold=args.stream_drift_threshold,
        factor_sharding=args.factor_sharding,
        comm_overlap=args.comm_overlap,
        staleness_budget=args.staleness_budget,
        service_devices=args.service_devices,
    )
    if sp > 1:
        lever_axes = ("data", "seq")
    elif shardwise_regime:
        lever_axes = ("data", "fsdp", "tensor")
    elif tp > 1:
        lever_axes = ("data", "tensor")
    else:
        lever_axes = ("data",)
    lever_env = planner.PlanEnv(
        # the carved curvature workers are not part of the training world
        world=int(devices.size) - max(0, args.service_devices),
        # factor replicas span the batch axes only: on the 3-D mesh that is
        # data×fsdp (the tensor axis holds distinct kernel shards, not
        # replicas); 0 keeps the legacy "same as world" meaning
        data_world=(devices.size // max(1, tp)) if shardwise_regime else 0,
        # a REAL seq axis is what the owner/comm levers cannot ride; the
        # tensor axis is replicated-compute and passes pure_dp
        mesh_axes=lever_axes,
        track_diagnostics=args.kfac_diagnostics,
        has_diag_a_layers=args.kfac_embedding,
        has_conv_layers=False,
        has_shard_lens_layers=bool(shardwise_regime and tp > 1),
        has_moe_layers=args.moe_experts > 0,
        fac_update_freq=max(1, args.kfac_cov_update_freq),
        kfac_update_freq=max(1, args.kfac_update_freq),
        service_devices=args.service_devices,
    )
    bad = planner.violations(cli_plan, lever_env)
    if bad:
        raise SystemExit(
            "invalid K-FAC lever composition:\n"
            + "\n".join(f"  [{r.name}] {r.message}" for r in bad)
        )
    # pure data-parallel runs use a one-axis mesh — the layout the
    # owner/comm levers require; sequence parallelism adds the seq axis;
    # --tensor-parallel builds the 2-D data×tensor mesh (replicated-compute
    # tensor axis, K-FAC collectives on 'data' only)
    service_workers = ()
    if args.service_devices > 0 and (sp > 1 or tp > 1 or shardwise_regime):
        raise SystemExit(
            "--service-devices carves a pure data-parallel mesh; it does "
            "not compose with --seq-parallel, --tensor-parallel or --fsdp"
        )
    if sp > 1:
        mesh = Mesh(devices.reshape(devices.size // sp, sp), ("data", "seq"))
        batch_spec = P("data", "seq")
        dp = devices.size // sp
    elif shardwise_regime:
        from kfac_pytorch_tpu.parallel.mesh import data_fsdp_tensor_mesh

        # 3-D data×fsdp×tensor mesh: batch rows spread over BOTH batch axes
        # (fsdp slots see distinct examples — parameter sharding, not
        # replication), kernels shard over 'tensor' via
        # shardwise.lm_param_shardings below
        mesh = data_fsdp_tensor_mesh(fsdp, max(1, tp), devices=devices)
        batch_spec = P(("data", "fsdp"))
        dp = devices.size // (fsdp * max(1, tp))
    elif tp > 1:
        from kfac_pytorch_tpu.parallel.mesh import data_tensor_mesh

        mesh = data_tensor_mesh(tp, devices=devices)
        batch_spec = P("data")
        dp = devices.size // tp
    elif args.service_devices > 0:
        from kfac_pytorch_tpu.parallel.mesh import split_service_mesh

        mesh, service_workers = split_service_mesh(
            args.service_devices, devices=list(devices.ravel())
        )
        devices = mesh.devices  # the training subset from here on
        batch_spec = P("data")
        dp = devices.size
    else:
        mesh = Mesh(devices, ("data",))
        batch_spec = P("data")
        dp = devices.size
    # batch rows shard over every batch axis: data only on the legacy
    # meshes, data×fsdp on the 3-D mesh
    batch_world = dp * fsdp if shardwise_regime else dp
    n_proc = launch.size()
    if batch_world % n_proc != 0:
        # per-process row-block slicing below assumes the batch axes span
        # processes contiguously; a seq axis spanning hosts needs a
        # different feed layout
        raise SystemExit(
            f"batch-axes size {batch_world} must be divisible by process "
            f"count {n_proc} (lower --seq-parallel so the sequence axis "
            "does not span hosts)"
        )
    global_bs = args.batch_size * batch_world
    if launch.is_primary():
        print(f"mesh data={dp} fsdp={fsdp} seq={sp} tensor={tp} "
              f"global_batch={global_bs} seq_len={args.seq_len}")

    if sp > 1:
        attn = make_context_parallel_attention(
            mesh, seq_axis="seq", batch_axis="data", kind=args.attention
        )
    else:
        # single-program attention: fused Pallas flash kernel on TPU,
        # exact jnp elsewhere (ops/flash_attention.py)
        from kfac_pytorch_tpu.ops.flash_attention import best_attention_fn

        attn = best_attention_fn()

    # data: WikiText token files or a Zipf-ish synthetic stream
    wt_dir = None if args.synthetic else data_lib.find_wikitext(args.data_dir)
    if wt_dir:
        splits, words = data_lib.build_corpus(wt_dir)
    else:
        if not args.synthetic and launch.is_primary():
            print("no WikiText data found; falling back to --synthetic")
        splits, words = data_lib.synthetic_corpus(vocab_size=1000)
    vocab = len(words)

    if args.model == "glm_moe_lite":
        import json

        if not args.model_config:
            raise SystemExit("--model glm_moe_lite needs --model-config")
        if sp > 1 or tp > 1 or shardwise_regime or args.moe_experts:
            raise SystemExit(
                "--model glm_moe_lite runs data-parallel (its expert-parallel "
                "share is in --model-config's held); no --seq-parallel, "
                "--tensor-parallel, --fsdp or --moe-experts"
            )
        with open(args.model_config) as f:
            sizes = {**json.load(f), "vocab_size": vocab}
    else:
        sizes = dict(
            vocab_size=vocab, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers,
            kfac_embedding=args.kfac_embedding, qkv_lens=args.qkv_lens,
            tie_embeddings=args.tie_embeddings,
            # legacy --tensor-parallel replicates compute, so the model stays
            # dense; the shardwise regime makes it a genuine Megatron MLP split
            tensor_parallel=tp if shardwise_regime else 1,
            moe_experts=args.moe_experts,
        )
    use_kfac = args.kfac_update_freq > 0
    built = build(
        args.model, sizes, global_batch=global_bs, seq_len=args.seq_len,
        attention_fn=attn, momentum=args.momentum, weight_decay=args.wd,
        grad_clip=args.grad_clip, remat=args.remat,
        kfac_kwargs=dict(
            factor_decay=args.stat_decay,
            damping=args.damping,
            kl_clip=args.kl_clip,
            fac_update_freq=args.kfac_cov_update_freq,
            kfac_update_freq=args.kfac_update_freq,
            mesh=mesh if devices.size > 1 else None,
            track_diagnostics=args.kfac_diagnostics,
            eigh_chunks=args.eigh_chunks,
            factor_comm_dtype=args.factor_comm_dtype,
            factor_comm_freq=args.factor_comm_freq,
            solver=args.solver,
            solver_rank=args.solver_rank,
            solver_auto_threshold=args.solver_auto_threshold,
            stream_drift_threshold=args.stream_drift_threshold,
            factor_sharding=args.factor_sharding,
            comm_overlap=args.comm_overlap,
            staleness_budget=args.staleness_budget,
            service_devices=args.service_devices,
            profile=args.profile,
            # the bank model's factors run on the inverse path alone
            **({"precond_method": "inverse"} if args.model == "glm_moe_lite" else {}),
        ) if use_kfac else None,
        step_kwargs=dict(
            mesh=mesh if args.grad_comm_dtype else None,
            grad_comm_dtype=jnp.bfloat16 if args.grad_comm_dtype == "bf16" else None,
        ),
    )
    model, init_toks, tx = built["model"], built["init_toks"], built["tx"]
    params = model.init(jax.random.PRNGKey(args.seed), init_toks, train=True)["params"]

    kfac = None
    kfac_sched = None
    if use_kfac:
        kfac_layers = built["layers"]
        build_kfac = lambda profile=args.profile: built["make_kfac"](profile=profile)
        kfac = built["kfac"]
        if kfac.plan is not None and launch.is_primary():
            drop = (
                f" (dropped: {', '.join(kfac.plan_dropped)})"
                if kfac.plan_dropped else ""
            )
            print(kfac.plan.describe() + drop)
        if args.autotune_steps and kfac.plan is not None:
            from _autotune import autotune_kfac

            def _fresh_state(k):
                # the train step donates its state (training/step.py), and
                # device_put to an already-matching sharding aliases — copy
                # so a timed candidate can't free the master params
                p = jax.tree_util.tree_map(
                    lambda x: jnp.array(x, copy=True), params
                )
                s = TrainState(
                    step=jnp.zeros((), jnp.int32), params=p,
                    batch_stats={}, opt_state=tx.init(p),
                    kfac_state=k.init(p),
                )
                if k.owner_sharded:
                    kstate = s.kfac_state
                    s = s.replace(kfac_state=None)
                    s = jax.device_put(s, NamedSharding(mesh, P()))
                    return s.replace(kfac_state=kstate)
                return jax.device_put(s, NamedSharding(mesh, P()))

            _build_step = built["make_step"]

            warm_rng = np.random.RandomState(args.seed)
            rows_local = global_bs // n_proc
            warm = put_sharded_batch(
                mesh,
                (warm_rng.randint(0, vocab, (rows_local, args.seq_len))
                 .astype(np.int32),
                 warm_rng.randint(0, vocab, (rows_local, args.seq_len))
                 .astype(np.int32)),
                batch_spec,
            )
            kfac, _ = autotune_kfac(
                kfac, build_kfac, _fresh_state, _build_step, warm,
                jnp.float32(args.base_lr), args.autotune_steps,
                broadcast=launch.broadcast_host_value,
                log=print if launch.is_primary() else None,
            )
        if args.damping_schedule:
            kfac_sched = KFACParamScheduler(
                kfac, damping_alpha=args.damping_alpha,
                damping_schedule=args.damping_schedule,
            )

    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats={},
        opt_state=tx.init(params),
        kfac_state=kfac.init(params) if kfac else None,
    )
    resume_from_epoch = 0
    if args.checkpoint_dir:
        state, resume_from_epoch = ckpt.auto_resume(args.checkpoint_dir, state)
        resume_from_epoch = int(launch.broadcast_host_value(resume_from_epoch))
    if kfac is not None and kfac.owner_sharded:
        # owner-mode placement contract: factor/eigen shards on their
        # owners (re-homing a restored checkpoint), the rest replicated
        kstate = ckpt.rehome_kfac_state(kfac, state.kfac_state)
        state = state.replace(kfac_state=None)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        state = state.replace(kfac_state=kstate)
    elif shardwise_regime and devices.size > 1:
        # shardwise placement contract (docs/SHARDING.md): kernels split
        # over tensor/fsdp (shardwise.lm_param_shardings), each per-shard
        # factor/eigen block on the devices holding the matching kernel
        # shard (KFAC.state_shardings); step counter, optimizer trace and
        # the remaining factors replicate
        from kfac_pytorch_tpu import shardwise

        shard_names = (
            kfac_layers if use_kfac
            else capture.discover_layers(model, init_toks, train=True)
        )
        pshard = shardwise.lm_param_shardings(state.params, shard_names, mesh)
        sharded_params = jax.device_put(state.params, pshard)
        kstate = state.kfac_state
        if kfac is not None:
            kstate = jax.device_put(kstate, kfac.state_shardings(kstate))
        state = state.replace(params=None, kfac_state=None)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        state = state.replace(params=sharded_params, kfac_state=kstate)
    else:
        state = jax.device_put(state, NamedSharding(mesh, P()))

    if args.grad_comm_dtype and sp > 1:
        raise SystemExit(
            "--grad-comm-dtype requires a pure data-parallel mesh "
            "(--seq-parallel 1): a sequence axis would make the per-device "
            "local forward see a partial example"
        )
    step_fn = built["make_step"](kfac)
    eval_fn = make_eval_step(model, eval_kwargs={"train": False})

    # [B_total, N] contiguous streams; segments of seq_len become samples.
    # Multi-host: every process derives the same global stream, then keeps
    # only its contiguous row block — make_array_from_process_local_data
    # (put_sharded_batch) assembles the global batch from those shards, so
    # no host may pass the full global batch.
    rows = global_bs // n_proc

    def local_rows(split):
        s = data_lib.batchify_tokens(splits[split], global_bs)
        return s[launch.rank() * rows : (launch.rank() + 1) * rows]

    def sharded_bptt_batches(stream):
        # shared train/val feed: BPTT segmentation (data_lib.bptt_batches)
        # device-put straight to the P(data, seq) layout
        for toks, tgts in data_lib.bptt_batches(stream, args.seq_len):
            with tel.span("comm/host_to_device"):
                batch = put_sharded_batch(
                    mesh,
                    (np.ascontiguousarray(toks), np.ascontiguousarray(tgts)),
                    batch_spec,
                )
            yield batch

    stream = local_rows("train")
    max_steps = (stream.shape[1] - 1) // args.seq_len
    steps_per_epoch = min(args.steps_per_epoch or max_steps, max_steps)

    writer = ScalarWriter(args.log_dir, enabled=jax.process_index() == 0)
    tel_writer = ScalarWriter(
        args.telemetry_dir,
        enabled=tel.enabled and launch.is_primary(),
        filename="telemetry.jsonl",
    )
    recompiles = RecompileMonitor(tel)
    recompiles.watch("train_step", step_fn, expected_step_variants(kfac))
    recompiles.watch("eval_step", eval_fn, 1)
    step = int(jax.device_get(state.step))
    # host-side refresh cadence: identical to kfac_flags_for_step at
    # --eigh-chunks 1, chunk/swap flags beyond (scheduler.EigenRefreshCadence)
    cadence = EigenRefreshCadence(kfac)
    if kfac is not None and getattr(kfac, "solver", "eigh") == "streaming":
        # drift signal for boundary decisions: one scalar device_get per
        # kfac_update_freq boundary, read off the LIVE state
        kfac.stream_drift_signal = lambda: float(
            jax.device_get(state.kfac_state["stream_residual"]))

    sup = None
    resume_skip = 0
    if args.preempt_save_dir:
        from kfac_pytorch_tpu import elastic

        sup = elastic.Supervisor(
            args.preempt_save_dir, snapshot_every=args.snapshot_every,
            kfac=kfac, cadence=cadence,
            heartbeat_every=max(1, args.snapshot_every or steps_per_epoch),
            fault_injector=elastic.maybe_injector(),
        )
        sup.install_signal_handlers()
        hit = sup.scan_resume(jax.device_get(state), params=state.params)
        if hit is not None:
            state, _manifest, step = hit
            # re-place exactly like a cold start: owner-sharded kfac_state
            # keeps the placement scan_resume gave it, everything else
            # (including replicated-mode kfac_state, which rehome passes
            # through as host arrays) is replicated over the mesh
            if kfac is not None and kfac.owner_sharded:
                kstate = state.kfac_state
                state = jax.device_put(
                    state.replace(kfac_state=None), NamedSharding(mesh, P())
                )
                state = state.replace(kfac_state=kstate)
            else:
                state = jax.device_put(state, NamedSharding(mesh, P()))
            resume_from_epoch = step // steps_per_epoch
            resume_skip = step % steps_per_epoch
            if launch.is_primary():
                print(f"elastic: resumed from snapshot at step {step}")
    preempted = False

    svc = None
    if kfac is not None and args.service_devices > 0:
        from kfac_pytorch_tpu.service import CurvatureService

        svc = CurvatureService(
            kfac, cadence, worker_devices=service_workers, supervisor=sup,
        )
        if launch.is_primary():
            print(
                f"curvature service: {len(service_workers)} worker "
                f"device(s), staleness budget {svc.staleness_budget}"
            )

    for epoch in range(resume_from_epoch, args.epochs):
        if kfac_sched:
            kfac_sched.step(epoch=epoch)
        t0 = time.perf_counter()
        loss_m = Metric("train/loss")
        diag_acc = {}  # kfac_* diagnostic key -> (sum, count)

        def eat(m):
            loss_m.update(m["loss"])
            if "kfac_spectrum_mass" in m:
                tel.set_gauge(
                    "kfac/spectrum_mass_captured",
                    float(m["kfac_spectrum_mass"]),
                )
            for k, v in m.items():
                if k.startswith("kfac_"):
                    s, c = diag_acc.get(k, (0.0, 0))
                    diag_acc[k] = (s + float(v), c + 1)

        # lag-window metric fetch: async dispatch, bounded in-flight batches
        pending = []
        with profiling.maybe_trace(args.log_dir, args.profile_epoch == epoch):
            for i, batch in enumerate(sharded_bptt_batches(stream)):
                if i >= steps_per_epoch:
                    break
                if epoch == resume_from_epoch and i < resume_skip:
                    continue  # mid-epoch snapshot resume: keep i == step phase
                flags = cadence.flags_for_step(step, epoch)
                if svc is not None:
                    # install the newest complete basis before the step
                    state = state.replace(
                        kfac_state=svc.before_step(step, state.kfac_state)
                    )
                if flags.get("eigen_chunk") is not None:
                    sp_t = tel.span("step/eigen_chunk")
                elif not flags.get("update_factors"):
                    sp_t = tel.span("step/plain")
                elif flags.get("update_eigen"):
                    sp_t = tel.span("step/eigen")
                else:
                    sp_t = tel.span("step/factors")
                with sp_t:
                    state, metrics = step_fn(
                        state, batch, jnp.float32(args.base_lr),
                        jnp.float32(kfac.hparams.damping if kfac else 0.0),
                        **flags
                    )
                    sp_t.block(metrics)
                if svc is not None:
                    # boundary steps publish the just-folded factor snapshot
                    svc.after_step(step, state.kfac_state)
                step += 1
                pending.append(metrics)
                if sup is not None and sup.on_step(step, lambda: state):
                    preempted = True
                    break
                if len(pending) > 2:
                    with tel.span("comm/device_get"):
                        m = jax.device_get(pending.pop(0))
                    eat(m)
            for m in jax.device_get(pending):
                eat(m)
        if preempted:
            if launch.is_primary():
                print(f"elastic: preempted; snapshot at step {step} saved")
            break
        dt = time.perf_counter() - t0
        ppl = float(np.exp(min(loss_m.avg, 20.0)))
        if launch.is_primary():
            tok_s = steps_per_epoch * global_bs * args.seq_len / dt
            print(f"epoch {epoch}: loss={loss_m.avg:.4f} ppl={ppl:.1f} {tok_s:.0f} tok/s ({dt:.1f}s)")
        writer.add_scalar("train/loss", loss_m.avg, epoch)
        writer.add_scalar("train/ppl", ppl, epoch)
        if diag_acc:
            means = {k: s / c for k, (s, c) in sorted(diag_acc.items())}
            for k, v in means.items():
                writer.add_scalar(f"kfac/{k[5:]}_mean", v, epoch)
            if launch.is_primary():
                print(
                    "  kfac: "
                    f"nu={means.get('kfac_nu', 0.0):.4f} "
                    f"cond_max={means.get('kfac_cond_max', 0.0):.3e} "
                    f"upd_cos={means.get('kfac_update_grad_cos', 0.0):.3f}"
                )

        if "valid" in splits:
            vl = Metric("val/loss")
            for vbatch in sharded_bptt_batches(local_rows("valid")):
                vl.update(jax.device_get(eval_fn(state, vbatch)["loss"]))
            vppl = float(np.exp(min(vl.avg, 20.0)))
            if launch.is_primary():
                print(f"  val: loss={vl.avg:.4f} ppl={vppl:.1f}")
            writer.add_scalar("val/loss", vl.avg, epoch)
            writer.add_scalar("val/ppl", vppl, epoch)

        if tel.enabled:
            p_plain = tel.percentiles("step/plain")
            p_fac = tel.percentiles("step/factors")
            p_eig = tel.percentiles("step/eigen")
            p_h2d = tel.percentiles("comm/host_to_device")
            if p_plain and p_fac:
                tel.set_gauge(
                    "phase/factor_ms", max(0.0, (p_fac[0] - p_plain[0]) * 1e3)
                )
            if p_fac and p_eig:
                tel.set_gauge(
                    "phase/eigh_ms", max(0.0, (p_eig[0] - p_fac[0]) * 1e3)
                )
            if p_h2d:
                tel.set_gauge("phase/comm_ms", p_h2d[0] * 1e3)
            excess = recompiles.check()
            if excess and launch.is_primary():
                print(f"  WARNING: unexpected recompiles (jit cache over "
                      f"budget): {excess}")
            if launch.is_primary():
                observability.write_prometheus(
                    os.path.join(args.telemetry_dir, "metrics.prom"), tel
                )
            observability.flush_jsonl(tel_writer, tel, epoch)

        if args.checkpoint_dir:
            ckpt.save_checkpoint(args.checkpoint_dir, epoch, state)

    if sup is not None:
        sup.wait()  # join any in-flight background snapshot write
    if tel.enabled:
        table = observability.summary_table(tel)  # collective: every rank
        if launch.is_primary():
            print("telemetry summary:")
            print(table)
    tel_writer.close()
    writer.close()
    return state


if __name__ == "__main__":
    main()
