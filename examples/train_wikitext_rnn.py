"""WikiText RNN/LSTM language-model training with K-FAC on TPU (JAX).

Flag-parity port of the reference trainer (examples/pytorch_wikitext_rnn.py)
— with the crucial difference that K-FAC actually works here: the reference
script is "work-in-progress and does not work with K-FAC yet"
(pytorch_wikitext_rnn.py:6) and crashes on stale kwargs when enabled
(SURVEY.md §2.2). The dense decoder is preconditioned; recurrent cells and
the embedding train with plain SGD (the reference's ``known_modules``
contract) unless ``--kfac-embedding`` adds the diagonal-A table — which
composes with ``--tied`` via the reduce lens (one statistics set over both
use sites). The K-FAC perf levers and the planner profiles share the same
flag surface as the other trainers.

Run:
    python examples/train_wikitext_rnn.py --synthetic --epochs 2
    python examples/train_wikitext_rnn.py --data-dir /path/to/wikitext-2
    python examples/train_wikitext_rnn.py --synthetic --profile production
"""

from __future__ import annotations

import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import _env  # noqa: F401  (platform forcing — must precede jax use)

import jax
import jax.numpy as jnp
import numpy as np

from kfac_pytorch_tpu import (
    KFAC,
    EigenRefreshCadence,
    KFACParamScheduler,
    capture,
    planner,
)
from kfac_pytorch_tpu.compile_cache import (
    RecompileMonitor,
    expected_step_variants,
)
from kfac_pytorch_tpu.models import wikitext_rnn
from kfac_pytorch_tpu.parallel import launch
from kfac_pytorch_tpu.training import checkpoint as ckpt
from kfac_pytorch_tpu.training import data as data_lib
from kfac_pytorch_tpu.training.lm_step import (
    init_carry,
    make_lm_eval_step,
    make_lm_train_step,
)
from kfac_pytorch_tpu.training.metrics import Metric, ScalarWriter
from kfac_pytorch_tpu.training.step import TrainState, make_sgd


def parse_args(argv=None):
    # Flag surface mirrors pytorch_wikitext_rnn.py:28-96.
    p = argparse.ArgumentParser(
        description="WikiText RNN K-FAC Example (TPU/JAX)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--data-dir", default=None, help="wikitext token dir")
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
    p.add_argument("--model", default="LSTM",
                   choices=list(wikitext_rnn.RNN_TYPES))
    p.add_argument("--emsize", type=int, default=650)
    p.add_argument("--nhid", type=int, default=650)
    p.add_argument("--nlayers", type=int, default=2)
    p.add_argument("--dropout", type=float, default=0.5)
    p.add_argument("--tied", action="store_true")
    p.add_argument("--kfac-embedding", action="store_true",
                   help="precondition the token embedding too (diagonal-A "
                        "K-FAC; beyond the reference's Linear/Conv2d set); "
                        "composes with --tied — the shared table then "
                        "accumulates ONE set of statistics over both the "
                        "lookup and the decoder use sites (reduce lens)")
    p.add_argument("--batch-size", type=int, default=20)
    p.add_argument("--bptt", type=int, default=35)
    p.add_argument("--epochs", type=int, default=40)
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--base-lr", type=float, default=20.0)
    p.add_argument("--lr-decay", nargs="+", type=int, default=[20, 30])
    p.add_argument("--momentum", type=float, default=0.0)
    p.add_argument("--wd", type=float, default=0.0)
    p.add_argument("--clip", type=float, default=0.25)
    p.add_argument("--kfac-update-freq", type=int, default=10, help="0 disables K-FAC")
    p.add_argument("--kfac-cov-update-freq", type=int, default=1)
    p.add_argument("--stat-decay", type=float, default=0.95)
    p.add_argument("--damping", type=float, default=0.003)
    p.add_argument("--kl-clip", type=float, default=0.001)
    # perf levers + planner, the same surface as the other trainers
    p.add_argument("--eigh-chunks", type=int, default=1,
                   help="pipeline the eigen refresh over this many steps "
                        "after each --kfac-update-freq boundary; 1 = "
                        "monolithic, bit-exact (docs/PERF.md)")
    p.add_argument("--factor-comm-dtype", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="wire dtype of the bucketed K-FAC factor exchange "
                        "(multi-device only; f32 = bitwise parity; int8 = "
                        "block-scaled codes + error feedback at 0.51x the "
                        "bf16 bytes, requires --factor-comm-freq > 1; "
                        "docs/PERF.md 'Sub-bf16 wire')")
    p.add_argument("--factor-comm-freq", type=int, default=1,
                   help="allreduce factor statistics every N capture steps "
                        "(multi-device only; 1 = per-step, exact)")
    p.add_argument("--factor-sharding", default="replicated",
                   choices=["replicated", "owner"],
                   help="owner: DP-KFAC owner-sharded curvature state — "
                        "O(model/devices) factor memory; embedding diag-A "
                        "factors shard as [vocab] vector slots, so "
                        "--kfac-embedding composes (docs/PERF.md)")
    p.add_argument("--solver", default="eigh",
                   choices=["eigh", "rsvd", "streaming"],
                   help="curvature eigensolver (rsvd: randomized truncated "
                        "refresh + Woodbury apply for big factor sides; "
                        "streaming: rsvd layout, per-step folds, drift-gated "
                        "re-orthonormalization)")
    p.add_argument("--solver-rank", type=int, default=128)
    p.add_argument("--solver-auto-threshold", type=int, default=512)
    p.add_argument("--stream-drift-threshold", type=float, default=0.05,
                   help="--solver streaming: re-orth at a boundary only when "
                        "the residual-mass gauge exceeds this (0 = every "
                        "boundary, periodic rsvd)")
    p.add_argument("--comm-overlap", action="store_true",
                   help="fuse the factor-statistics reduction into the "
                        "gradient stream (multi-device only; bitwise-"
                        "identical numerics)")
    p.add_argument("--staleness-budget", type=int, default=0,
                   help="bounded slip for deferred flushes / pending swaps "
                        "/ service basis installs (needs --factor-comm-freq "
                        "> 1, --eigh-chunks > 1 or --service-devices > 0)")
    p.add_argument("--service-devices", type=int, default=0,
                   help="carve this many devices out as dedicated curvature "
                        "workers (kfac_pytorch_tpu/service/): the eigen "
                        "refresh leaves the training step; bases install "
                        "between steps at bounded staleness "
                        "(docs/SERVICE.md); 0 = inline refresh")
    p.add_argument("--profile", default=None,
                   choices=["safe", "memory", "production"],
                   help="resolve the K-FAC perf levers from a named planner "
                        "profile using this model's factor shapes; explicit "
                        "lever flags win (docs/PLANNER.md)")
    p.add_argument("--grad-comm-dtype", default=None, choices=[None, "bf16"],
                   help="downcast the per-step data-parallel gradient mean "
                        "on the wire (the reference's --fp16-allreduce on "
                        "DistributedOptimizer); None = exact f32 reduction")
    p.add_argument("--seed", type=int, default=42)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)

    wt_dir = None if args.synthetic else data_lib.find_wikitext(args.data_dir)
    if wt_dir:
        splits, vocab = data_lib.build_corpus(wt_dir)
        print(f"wikitext from {wt_dir}: vocab={len(vocab)}")
    else:
        if not args.synthetic:
            print("no wikitext data found; falling back to --synthetic")
        splits, vocab = data_lib.synthetic_corpus()
    ntokens = len(vocab)

    train_stream = data_lib.batchify_tokens(splits["train"], args.batch_size)
    val_stream = data_lib.batchify_tokens(
        splits.get("valid", splits["train"]), args.batch_size
    )

    model = wikitext_rnn.get_model(
        args.model, ntokens, args.emsize, args.nhid, args.nlayers,
        args.dropout, args.tied, kfac_embedding=args.kfac_embedding,
    )
    tokens0 = jnp.zeros((args.batch_size, args.bptt), jnp.int32)
    variables = model.init(
        {"params": jax.random.PRNGKey(args.seed), "dropout": jax.random.PRNGKey(1)},
        tokens0, train=True,
    )
    params = variables["params"]

    tx = make_sgd(momentum=args.momentum, weight_decay=args.wd)
    use_kfac = args.kfac_update_freq > 0
    kfac = None
    devices = np.asarray(jax.devices())
    mesh = None
    service_workers = ()
    if use_kfac:
        layers = capture.discover_layers(model, tokens0, train=True)
        if not layers:
            print("WARNING: no preconditionable layers (tied decoder?); "
                  "running plain SGD")
            use_kfac = False
        else:
            print(f"K-FAC layers: {layers}")
            # CLI lever composition routed through the planner's validity
            # matrix, same as the transformer trainer — refusals carry the
            # matrix's reasons instead of ad-hoc SystemExits
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
            lever_env = planner.PlanEnv(
                # carved curvature workers leave the training world
                world=int(devices.size) - max(0, args.service_devices),
                mesh_axes=("data",) if devices.size > 1 else (),
                has_diag_a_layers=args.kfac_embedding,
                has_conv_layers=False,
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
            if args.service_devices > 0:
                from kfac_pytorch_tpu.parallel.mesh import split_service_mesh

                mesh, service_workers = split_service_mesh(
                    args.service_devices
                )
            elif devices.size > 1:
                from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh

                mesh = data_parallel_mesh()
            profile_shapes = None
            if args.profile:
                profile_shapes = planner.model_facts(params, layers=layers)
            kfac = KFAC(
                layers=layers,
                factor_decay=args.stat_decay,
                damping=args.damping,
                kl_clip=args.kl_clip,
                fac_update_freq=args.kfac_cov_update_freq,
                kfac_update_freq=args.kfac_update_freq,
                mesh=mesh,
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
                profile_shapes=profile_shapes,
            )
            if kfac.plan is not None:
                drop = (
                    f" (dropped: {', '.join(kfac.plan_dropped)})"
                    if kfac.plan_dropped else ""
                )
                print(kfac.plan.describe() + drop)

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
        # hosts must agree on the resume epoch (checkpoints may be
        # host-local; the reference broadcasts it too,
        # pytorch_imagenet_resnet.py:136-140) — differing start epochs
        # would desync the per-step collectives
        resume_from_epoch = int(launch.broadcast_host_value(resume_from_epoch))
    if kfac is not None and kfac.owner_sharded:
        # owner-mode placement contract: factor/eigen shards on their
        # owners (re-homing a restored checkpoint), the rest replicated
        from jax.sharding import NamedSharding, PartitionSpec as P

        kstate = ckpt.rehome_kfac_state(kfac, state.kfac_state)
        state = state.replace(kfac_state=None)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        state = state.replace(kfac_state=kstate)

    if args.grad_comm_dtype and mesh is None:
        from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh

        mesh = data_parallel_mesh()
    if mesh is not None and (kfac is None or not kfac.owner_sharded):
        # Commit the state to the mesh up front (replicated), like the
        # transformer trainer: a step whose K-FAC plane carries a mesh
        # returns mesh-committed arrays, so feeding uncommitted inputs on
        # the first call (and uncommitted carries each epoch) would retrace
        # every flag variant once more after the placements settle.
        from jax.sharding import NamedSharding, PartitionSpec as P

        state = jax.device_put(state, NamedSharding(mesh, P()))
    comm_active = (
        kfac is not None
        and kfac.factor_comm is not None
        and kfac.factor_comm.active
    )
    if (args.grad_comm_dtype or comm_active) and mesh is not None:
        if args.batch_size % mesh.devices.size:
            raise SystemExit(
                f"the sharded train step splits the batch over "
                f"{mesh.devices.size} devices; --batch-size "
                f"{args.batch_size} must divide evenly"
            )
    train_step = make_lm_train_step(
        model, tx, kfac, grad_clip=args.clip,
        mesh=mesh if args.grad_comm_dtype else None,
        grad_comm_dtype=jnp.bfloat16 if args.grad_comm_dtype == "bf16" else None,
    )
    eval_step = make_lm_eval_step(model)

    writer = ScalarWriter(args.log_dir)
    recompiles = RecompileMonitor()
    recompiles.watch("train_step", train_step, expected_step_variants(kfac))
    step = int(jax.device_get(state.step))
    rng = jax.random.PRNGKey(args.seed)
    # host-side refresh cadence: identical to kfac_flags_for_step at
    # --eigh-chunks 1, chunk/swap flags beyond (scheduler.EigenRefreshCadence)
    cadence = EigenRefreshCadence(kfac)
    if kfac is not None and getattr(kfac, "solver", "eigh") == "streaming":
        # drift signal for boundary decisions: one scalar device_get per
        # kfac_update_freq boundary, read off the LIVE state
        kfac.stream_drift_signal = lambda: float(
            jax.device_get(state.kfac_state["stream_residual"]))
    max_steps = (train_stream.shape[1] - 1) // args.bptt
    steps_per_epoch = min(args.steps_per_epoch or max_steps, max_steps)

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
            # re-place exactly like a cold start (stray host-numpy leaves
            # would compile the step once more): owner-sharded kfac_state
            # keeps the placement scan_resume gave it, everything else is
            # replicated / default-device
            if kfac is not None and kfac.owner_sharded:
                from jax.sharding import NamedSharding, PartitionSpec as P

                kstate = state.kfac_state
                state = jax.device_put(
                    state.replace(kfac_state=None), NamedSharding(mesh, P())
                )
                state = state.replace(kfac_state=kstate)
            elif mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                state = jax.device_put(state, NamedSharding(mesh, P()))
            else:
                state = jax.device_put(state)
            resume_from_epoch = step // steps_per_epoch
            resume_skip = step % steps_per_epoch
            print(f"elastic: resumed from snapshot at step {step}")
    preempted = False

    svc = None
    if kfac is not None and args.service_devices > 0:
        from kfac_pytorch_tpu.service import CurvatureService

        svc = CurvatureService(
            kfac, cadence, worker_devices=service_workers, supervisor=sup,
        )
        print(f"curvature service: {len(service_workers)} worker device(s), "
              f"staleness budget {svc.staleness_budget}")

    def fresh_carry():
        # zero carry for an epoch start, committed to the mesh so epoch
        # boundaries don't introduce a mixed committed/uncommitted input
        # signature (one spurious train_step retrace per epoch otherwise)
        carry = init_carry(model, jax.device_get(state.params), tokens0)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            carry = jax.device_put(carry, NamedSharding(mesh, P()))
        return carry

    for epoch in range(resume_from_epoch, args.epochs):
        lr = args.base_lr
        for e in args.lr_decay:
            if epoch >= e:
                lr *= 0.25  # torch LM convention: anneal lr /4 at plateaus
        carry = fresh_carry()
        loss_m = Metric("train/loss")
        t0 = time.perf_counter()
        n_steps = 0
        for i, (xb, yb) in enumerate(
            data_lib.bptt_batches(train_stream, args.bptt)
        ):
            if i >= steps_per_epoch:
                break
            rng, sub = jax.random.split(rng)
            if epoch == resume_from_epoch and i < resume_skip:
                continue  # mid-epoch snapshot resume: keep i/rng == step phase
            flags = cadence.flags_for_step(step, epoch)
            if svc is not None:
                # install the newest complete basis before the step
                state = state.replace(
                    kfac_state=svc.before_step(step, state.kfac_state)
                )
            state, carry, metrics = train_step(
                state, (jnp.asarray(xb), jnp.asarray(yb)), carry, sub,
                jnp.float32(lr), jnp.float32(kfac.hparams.damping if kfac else 0.0),
                **flags,
            )
            if svc is not None:
                # boundary steps publish the just-folded factor snapshot
                svc.after_step(step, state.kfac_state)
            step += 1
            n_steps += 1
            loss_m.update(jax.device_get(metrics["loss"]))
            if sup is not None and sup.on_step(step, lambda: state):
                preempted = True
                break
        if preempted:
            print(f"elastic: preempted; snapshot at step {step} saved")
            break
        dt = time.perf_counter() - t0
        ppl = math.exp(min(loss_m.avg, 20))
        print(f"epoch {epoch}: loss={loss_m.avg:.4f} ppl={ppl:.1f} "
              f"lr={lr:.2f} ({n_steps} steps, {dt:.1f}s)")
        writer.add_scalar("train/loss", loss_m.avg, epoch)
        writer.add_scalar("train/ppl", ppl, epoch)
        excess = recompiles.check()
        if excess:
            print(f"  WARNING: unexpected recompiles (jit cache over "
                  f"budget): {excess}")

        vcarry = fresh_carry()
        vl = Metric("val/loss")
        for xb, yb in data_lib.bptt_batches(val_stream, args.bptt):
            m, vcarry = eval_step(state, (jnp.asarray(xb), jnp.asarray(yb)), vcarry)
            vl.update(jax.device_get(m["loss"]))
        vppl = math.exp(min(vl.avg, 20))
        print(f"  val: loss={vl.avg:.4f} ppl={vppl:.1f}")
        writer.add_scalar("val/loss", vl.avg, epoch)
        writer.add_scalar("val/ppl", vppl, epoch)

        if args.checkpoint_dir:
            ckpt.save_checkpoint(args.checkpoint_dir, epoch, state)

    if sup is not None:
        sup.wait()  # join any in-flight background snapshot write
    writer.close()
    return state


if __name__ == "__main__":
    main()
