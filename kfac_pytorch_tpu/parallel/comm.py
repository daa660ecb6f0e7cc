"""Factor-communication plane: bucketed, compressed, deferrable allreduce.

The reference exchanges K-FAC factor statistics with one allreduce per layer
per factor (kfac_preconditioner.py:410-419 — an ``hvd.allreduce`` for every
A and every G), and the train steps here reproduced that faithfully: each
capture step issued a separate f32 ``lax.pmean`` per layer per factor inside
the compressed-grad ``shard_map``. This module replaces those per-layer
pmeans with one plane owning all three wire levers:

* **Tensor fusion** — every per-layer A/G stat leaf flattens into a small
  static set of flat buckets (``parallel.assignment.plan_factor_buckets``)
  and ONE collective moves each bucket (SPD-KFAC, arxiv 2107.06533: fused
  factor communication is the dominant distributed-K-FAC lever once compute
  is optimized). ``scripts/check_collective_count.py`` pins the compiled
  capture step to ≤ bucket-count factor all-reduces.
* **Wire compression** — ``KFAC(factor_comm_dtype="bf16")`` casts only the
  bucket payload for the wire; the f32 running-average master copy on device
  is untouched (the factor-side mirror of ``training.step.pmean_compressed``).
* **Deferred reduction** — ``KFAC(factor_comm_freq=N)`` skips the per-step
  contribution reduction entirely: every replica EMAs its LOCAL statistics,
  and the merged running averages cross the wire only every N capture steps
  and always immediately before an eigen refresh (DP-KFAC, arxiv 2206.15143:
  locally-averaged factors suffice between refreshes). The merge itself is
  ``ops.factors.merge_running_avg_buckets`` — exact for lockstep replicas
  because the EMA is linear in its contributions.

Escape hatches: every knob defaults to the pre-plane behavior. With
``factor_comm_dtype="f32"`` and ``factor_comm_freq=1`` on a single device
(or without a mesh) the plane is inert and the train step's program is
untouched; inside the compressed-grad wrapper the f32 bucketed mean is
bitwise-identical to the per-layer pmeans it replaced
(tests/test_factor_comm.py pins both, with
:func:`per_layer_pmean_reference` kept as the oracle).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from kfac_pytorch_tpu import capture, compat
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.observability.telemetry import get_telemetry
from kfac_pytorch_tpu.ops import factors as factor_ops
from kfac_pytorch_tpu.parallel.assignment import (
    FactorBucket,
    plan_factor_buckets,
)

PyTree = Any

_F32 = np.dtype(np.float32)
_INT8 = np.dtype(np.int8)

# Block-scaled int8 wire (KFAC(factor_comm_dtype="int8")): each bucket is
# quantized per contiguous 256-element block against its own max-abs scale.
# 256 keeps the scale overhead at 4/256 = 1.6% of the payload (int8 wire ≈
# 0.51x the bf16 bytes) while bounding the dynamic range one scale must
# cover — A and G statistics of different layers sharing a bucket can sit
# orders of magnitude apart, and a single per-bucket scale would crush the
# small ones to zero codes.
_QUANT_BLOCK = 256
# Stochastic rounding follows the repo's deterministic-PRNG convention
# (ops/rsvd.py _SKETCH_SEED): one fixed, dated base seed, discriminated by
# fold_in — here per flush step and per bucket — so reruns are bit-exact
# and no per-device randomness exists (each replica rounds its OWN payload;
# the shared key stream is deterministic, the data differ).
_QUANT_SEED = 21070653  # arxiv 2107.06533 (SPD-KFAC), the wire-lever lineage


def quantize_bucket(
    buf: jnp.ndarray, key: jax.Array
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Block-scaled stochastic int8 quantization of one flat f32 bucket.

    Returns ``(codes [nblocks, 256] int8, scales [nblocks, 1] f32)``. The
    rounding is ``floor(x/scale + u)`` with ``u ~ U[0, 1)`` — unbiased
    (``E[q]·scale = x``), which is what lets the EMA-linearity argument that
    justified the bf16 wire extend down to 8 bits: the quantization noise
    is zero-mean per step and the error-feedback accumulator re-injects
    whatever a single step did round away. An all-zero block quantizes
    against scale 1.0 to zero codes (exact).
    """
    n = int(buf.shape[0])
    pad = (-n) % _QUANT_BLOCK
    x = jnp.pad(buf, (0, pad)) if pad else buf
    blocks = x.reshape(-1, _QUANT_BLOCK)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    u = jax.random.uniform(key, blocks.shape, jnp.float32)
    codes = jnp.clip(jnp.floor(blocks / scale + u), -127.0, 127.0)
    return codes.astype(jnp.int8), scale


def dequantize_bucket(
    codes: jnp.ndarray, scale: jnp.ndarray, n: int
) -> jnp.ndarray:
    """Inverse of :func:`quantize_bucket`: f32 ``[n]`` bucket payload."""
    return (codes.astype(jnp.float32) * scale).reshape(-1)[:n]


def quant_wire_bytes(sizes: List[int]) -> int:
    """Exact int8 wire bytes for bucket payload sizes: 1 byte per element
    plus 4 bytes per 256-element block scale."""
    return sum(s + (-(-s // _QUANT_BLOCK)) * 4 for s in sizes)


def publish_wire_quant_error(wire_error: Dict[str, jnp.ndarray]) -> float:
    """Host-side: global L2 norm of the error-feedback residuals onto the
    ``kfac/wire_quant_error_norm`` gauge (docs/OBSERVABILITY.md). A norm
    that trends upward instead of hovering means the int8 wire is
    systematically fighting the factor dynamics — widen the wire."""
    total = 0.0
    for v in wire_error.values():
        total += float(jnp.sum(jnp.square(jnp.asarray(v, jnp.float32))))
    norm = float(np.sqrt(total))
    get_telemetry().set_gauge("kfac/wire_quant_error_norm", norm)
    return norm


def flatten_buckets(
    leaves: List[jnp.ndarray], plan: Tuple[FactorBucket, ...]
) -> List[jnp.ndarray]:
    """Pack stat leaves into the plan's flat wire buffers."""
    bufs = []
    for bucket in plan:
        parts = [leaves[e.index].reshape(-1) for e in bucket.entries]
        bufs.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
    return bufs


def unflatten_buckets(
    bufs: List[jnp.ndarray],
    plan: Tuple[FactorBucket, ...],
    like_leaves: List[jnp.ndarray],
) -> List[jnp.ndarray]:
    """Slice bucket buffers back into leaves (inverse of flatten_buckets).

    ``like_leaves`` supplies leaves for any index the plan does not cover —
    the plan always covers all of them, but taking the template makes the
    round-trip contract explicit and testable.
    """
    out = list(like_leaves)
    for bucket, buf in zip(plan, bufs):
        for e in bucket.entries:
            out[e.index] = buf[e.offset : e.offset + e.size].reshape(e.shape)
    return out


def per_layer_pmean_reference(tree: PyTree, axis_name: str) -> PyTree:
    """The pre-plane wire op — one f32 pmean per stat leaf.

    Kept (unused by production code) as the parity oracle: the bucketed f32
    path must stay bitwise-identical to this (tests/test_factor_comm.py).
    """
    return jax.tree_util.tree_map(lambda x: lax.pmean(x, axis_name), tree)


def ring_allreduce_mean(
    buf: jnp.ndarray, axis_name: str, world: int, wire_dtype=None
) -> jnp.ndarray:
    """Chunked ppermute ring mean of one flat bucket — the overlap plane's
    scheduler-visibility fallback.

    XLA may serialize independent all-reduces onto one collective stream,
    re-hiding nothing; a ring of ``world-1`` ppermute+add hops
    (reduce-scatter phase) followed by ``world-1`` ppermute hops (allgather
    phase) expresses the same mean as many small point-to-point transfers
    the latency-hiding scheduler can weave between compute. The sum is
    associated in ring order, so the result is within reduction-
    reassociation tolerance of ``lax.pmean`` — NOT bitwise — which is why
    this path is opt-in (``KFAC_OVERLAP_PPERMUTE=1``) while the default
    fused overlap mode keeps the exact psum.
    """
    if world <= 1:
        return buf
    orig_dtype = buf.dtype
    n = int(buf.shape[0])
    pad = (-n) % world
    if pad:
        buf = jnp.concatenate([buf, jnp.zeros((pad,), buf.dtype)])
    if wire_dtype is not None:
        buf = buf.astype(wire_dtype)
    acc = buf.reshape(world, -1)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % world) for i in range(world)]
    # reduce-scatter: in hop s device d forwards its partial of chunk
    # (d-s) mod world and folds the incoming partial of chunk (d-s-1) mod
    # world; after world-1 hops device d owns the FULL sum of chunk
    # (d+1) mod world.
    for s in range(world - 1):
        send = jnp.take(acc, jnp.mod(idx - s, world), axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        acc = acc.at[jnp.mod(idx - s - 1, world)].add(recv)
    # allgather: circulate each completed chunk the rest of the way round
    for s in range(world - 1):
        send = jnp.take(acc, jnp.mod(idx + 1 - s, world), axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        acc = acc.at[jnp.mod(idx - s, world)].set(recv)
    out = (acc.reshape(-1).astype(jnp.float32) / world).astype(orig_dtype)
    return out[:n] if pad else out


class FactorComm:
    """The factor-statistics exchange plane of one ``KFAC`` instance.

    Owns the static bucket layout (cached per stat-tree signature), the wire
    dtype, and the deferral policy. Two entry points:

    * :meth:`exchange_contribs` — the per-capture-step exchange, called
      INSIDE the train step's ``shard_map`` where the reduction axis is
      bound. Deferred mode makes it a no-op (statistics stay local).
    * :meth:`flush` — the deferred-mode merge of the per-replica factor
      running averages, called from ``KFAC.update`` in the GSPMD region
      (it opens its own replicated ``shard_map``).

    Trace-time wire accounting lands in the ``kfac/factor_wire_bytes`` and
    ``kfac/factor_collectives`` gauges (docs/OBSERVABILITY.md) and on
    ``last_wire_bytes``/``last_collectives`` for host-side readers (bench).
    """

    def __init__(
        self,
        mesh=None,
        axis_name: str = "data",
        comm_dtype: Any = jnp.float32,
        comm_freq: int = 1,
        max_bucket_elems: int = 1 << 20,
        sharded: bool = False,
        overlap: bool = False,
    ):
        if int(comm_freq) < 1:
            raise ValueError(f"Invalid factor_comm_freq: {comm_freq}")
        self.mesh = mesh
        self.axis_name = axis_name
        self.comm_dtype = np.dtype(comm_dtype)
        self.comm_freq = int(comm_freq)
        self.max_bucket_elems = int(max_bucket_elems)
        self.sharded = bool(sharded)
        # Overlap plane (KFAC(comm_overlap=True)): issue the factor-bucket
        # reductions interleaved with the gradient stream, in backward-layer
        # (reversed-bucket) order. Fused mode keeps the exact per-bucket
        # psum; KFAC_OVERLAP_PPERMUTE=1 selects the ring fallback
        # (ring_allreduce_mean) when XLA serializes the fused collectives.
        self.overlap = bool(overlap)
        self.overlap_ppermute = self.overlap and os.environ.get(
            "KFAC_OVERLAP_PPERMUTE", ""
        ) not in ("", "0")
        self.last_wire_bytes: Optional[int] = None
        self.last_collectives: Optional[int] = None
        self._plans: Dict[Any, Tuple[FactorBucket, ...]] = {}

    # -- policy ---------------------------------------------------------

    def _axis_world(self, axis) -> int:
        """Replica count along the factor axis — a product when ``axis`` is
        a tuple (3-D data×fsdp×tensor meshes reduce factors over BOTH
        batch-carrying axes; see training.step.require_pure_dp_mesh)."""
        if self.mesh is None:
            return 1
        axes = axis if isinstance(axis, tuple) else (axis,)
        world = 1
        hit = False
        for a in axes:
            if a in self.mesh.shape:
                hit = True
                world *= int(self.mesh.shape[a])
        return world if hit else int(self.mesh.devices.size)

    @property
    def multi_device(self) -> bool:
        """More than one replica along the FACTOR axis (the product of the
        batch-carrying axes when ``axis_name`` is a tuple). On a 2-D
        data×tensor mesh only the data axis carries K-FAC collectives, so a
        mesh that is multi-device purely in its tensor axis leaves the plane
        inert."""
        if self.mesh is None:
            return False
        return self._axis_world(self.axis_name) > 1

    @property
    def defer(self) -> bool:
        """Deferred reduction on: statistics accumulate locally between
        flushes. Requires the KFAC mesh (flush opens a shard_map over it)."""
        return self.comm_freq > 1 and self.multi_device

    @property
    def active(self) -> bool:
        """True when the plane changes the wire vs. the defaults — the train
        steps then route the capture computation through the explicit-
        collective wrapper even without ``grad_comm_dtype``. Owner-sharded
        mode (``factor_sharding="owner"``) is always active: statistics must
        stay local at capture so the reduce-scatter can land each layer's
        mean only on its owner. Overlap mode is active for the same
        structural reason: the fused issue order only exists inside the
        explicit wrapper where the factor and gradient collectives share a
        trace."""
        return self.multi_device and (
            self.defer or self.comm_dtype != _F32 or self.sharded
            or self.overlap
        )

    @property
    def quantized(self) -> bool:
        """Sub-bf16 wire: the bucket payload crosses as block-scaled int8
        codes + f32 scales, with per-replica error feedback. Only legal on
        the deferred path (``KFAC.__init__`` refuses int8 at
        ``factor_comm_freq=1`` — the per-step contribution exchange has no
        state slot to carry the residual in)."""
        return self.comm_dtype == _INT8

    @property
    def overlap_mode(self) -> int:
        """The kfac/overlap_mode gauge value: 0 = off (serial), 1 = fused
        psum stream, 2 = ppermute ring fallback."""
        if not (self.overlap and self.multi_device):
            return 0
        return 2 if self.overlap_ppermute else 1

    # -- plan -----------------------------------------------------------

    def _plan_for(self, leaves: List[jnp.ndarray]) -> Tuple[FactorBucket, ...]:
        key = tuple(tuple(leaf.shape) for leaf in leaves)
        plan = self._plans.get(key)
        if plan is None:
            plan = plan_factor_buckets(
                [leaf.shape for leaf in leaves], self.max_bucket_elems
            )
            self._plans[key] = plan
        sizes = [b.size for b in plan]
        if self.quantized:
            # exact accounting: int8 codes plus the per-block f32 scales
            # (planner/cost_model.plan_wire_bytes mirrors this formula, and
            # planner/drift.py normalizes measurements back to f32-equivalent
            # before comparing, so plan_drift_wire_bytes stays 1.0)
            wire = quant_wire_bytes(sizes)
        else:
            wire = sum(sizes) * self.comm_dtype.itemsize
        tel = get_telemetry()
        tel.set_gauge("kfac/factor_wire_bytes", wire)
        tel.set_gauge("kfac/factor_collectives", len(plan))
        self.last_wire_bytes = wire
        self.last_collectives = len(plan)
        return plan

    # -- wire ops -------------------------------------------------------

    def allreduce(self, tree: PyTree, axis_name: Optional[str] = None) -> PyTree:
        """Bucketed cross-replica mean of a stat pytree.

        Must run where ``axis_name`` is bound (inside a ``shard_map``). The
        flatten/concat around the collective are trace-time reshapes XLA
        folds into the buffer layout; the mean itself (with the optional
        wire downcast) is ``ops.factors.merge_running_avg_buckets``.
        """
        axis = axis_name or self.axis_name
        if self.quantized:
            raise ValueError(
                "int8 factor wire routes through FactorComm.flush(..., "
                "wire_error=...) only — the plain bucketed pmean cannot "
                "reduce int8 codes"
            )
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        with phase("kfac_exchange", "trace/kfac/factor_comm"):
            plan = self._plan_for(leaves)
            wire_dtype = None if self.comm_dtype == _F32 else self.comm_dtype
            bufs = flatten_buckets(leaves, plan)
            if self.overlap:
                # Backward-layer issue order: bucket entries follow leaf
                # (forward traversal) order, so issuing the buckets reversed
                # puts the LAST layers' statistics — ready first during
                # backprop — on the wire first. Each bucket's mean is
                # independent of issue position, so the values are bitwise
                # those of the serial order; only the schedule changes.
                order = list(range(len(bufs)))[::-1]
                # the ppermute ring needs ONE named axis (lax.ppermute does
                # not linearize tuples); tuple-axis meshes keep the exact
                # fused psum stream
                if self.overlap_ppermute and not isinstance(axis, tuple):
                    world = self._axis_world(axis)
                    merged = [
                        ring_allreduce_mean(bufs[i], axis, world, wire_dtype)
                        for i in order
                    ]
                else:
                    merged = factor_ops.merge_running_avg_buckets(
                        [bufs[i] for i in order], axis, wire_dtype
                    )
                out: List[Optional[jnp.ndarray]] = [None] * len(bufs)
                for j, i in enumerate(order):
                    out[i] = merged[j]
                bufs = out
            else:
                bufs = factor_ops.merge_running_avg_buckets(
                    bufs, axis, wire_dtype
                )
            leaves = unflatten_buckets(bufs, plan, leaves)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def exchange_contribs(
        self,
        a_contribs: Dict[str, jnp.ndarray],
        g_stats: Dict[str, jnp.ndarray],
        axis_name: str,
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Per-capture-step exchange point inside the train step's shard_map.

        Fuses the A and G dicts into one stat tree so both factors share
        buckets. Deferred mode returns the LOCAL statistics unchanged —
        each replica's running averages then evolve independently until
        :meth:`flush` merges them. Owner-sharded mode also returns locals:
        the reduce-scatter in :meth:`scatter_merge` is the exchange, and it
        runs from ``KFAC.update`` where the factor shards are in scope.
        """
        if self.defer or self.sharded:
            return a_contribs, g_stats
        tree = capture.factor_stat_tree(a_contribs, g_stats)
        tree = self.allreduce(tree, axis_name)
        return capture.split_factor_stat_tree(tree)

    def wire_error_init(self, facs: PyTree) -> Dict[str, jnp.ndarray]:
        """Zero error-feedback residuals, one f32 buffer per wire bucket.

        Keyed ``"b<i>"`` by bucket index — the bucket plan is a pure
        function of the stat-tree leaf shapes, so the keys are stable
        across restarts and the buffers snapshot/restore like any other
        state (they are REPLICA-LOCAL data: ``elastic/state_io.py`` packs
        them per replica exactly like the deferred ``factor_local`` tree).
        """
        leaves, _ = jax.tree_util.tree_flatten(facs)
        plan = plan_factor_buckets(
            [leaf.shape for leaf in leaves], self.max_bucket_elems
        )
        return {
            f"b{i}": jnp.zeros((b.size,), jnp.float32)
            for i, b in enumerate(plan)
        }

    def _merge_quantized(
        self,
        tree: PyTree,
        wire_error: Dict[str, jnp.ndarray],
        seed: jnp.ndarray,
    ) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
        """Int8 bucket merge with error feedback (inside the shard_map).

        Per bucket: fold the carried residual into the payload, quantize
        (block-scaled, stochastically rounded), put ONLY the int8 codes and
        the per-block f32 scales on the wire (``lax.all_gather`` — a psum
        would have to widen the codes before they ever left the device),
        and dequantize+average locally. The new residual is this replica's
        payload minus its own dequantized codes — what the OTHER replicas
        just received wrong from us and will be compensated for at the next
        flush (error feedback, per-replica divergent state).
        """
        axis = self.axis_name
        world = self._axis_world(axis)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        with phase("kfac_exchange", "trace/kfac/factor_comm"):
            plan = self._plan_for(leaves)
            bufs = flatten_buckets(leaves, plan)
            base = jax.random.fold_in(
                jax.random.PRNGKey(_QUANT_SEED), seed
            )
            merged: List[jnp.ndarray] = []
            new_error: Dict[str, jnp.ndarray] = {}
            for i, buf in enumerate(bufs):
                n = int(buf.shape[0])
                payload = buf.astype(jnp.float32) + wire_error[f"b{i}"]
                codes, scale = quantize_bucket(
                    payload, jax.random.fold_in(base, i)
                )
                new_error[f"b{i}"] = payload - dequantize_bucket(
                    codes, scale, n
                )
                all_codes = lax.all_gather(codes, axis)
                all_scale = lax.all_gather(scale, axis)
                mean = (
                    jnp.sum(
                        all_codes.astype(jnp.float32) * all_scale, axis=0
                    )
                    / world
                )
                merged.append(mean.reshape(-1)[:n].astype(buf.dtype))
            leaves = unflatten_buckets(merged, plan, leaves)
        return jax.tree_util.tree_unflatten(treedef, leaves), new_error

    def flush(
        self,
        facs: PyTree,
        wire_error: Optional[Dict[str, jnp.ndarray]] = None,
        seed: Optional[jnp.ndarray] = None,
    ):
        """Merge the per-replica factor running averages (deferred mode).

        Runs in the GSPMD region of the jitted step: between flushes the
        factors are *annotated* fully-replicated but physically diverged
        (every device EMA'd its own local contributions — elementwise ops on
        replicated arrays execute per-device, no collective resyncs them),
        so a ``shard_map`` with replicated specs hands each device its own
        copy and one bucketed pmean produces the uniform-weight merge.

        With an int8 wire the caller supplies the error-feedback residuals
        (``wire_error``, from KFAC state) and the deterministic rounding
        discriminator (``seed``, the step counter); the return value is then
        ``(facs, new_wire_error)`` instead of ``facs``.
        """
        if not self.defer:
            raise ValueError(
                "FactorComm.flush() requires deferred factor communication "
                "(factor_comm_freq > 1 with a multi-device KFAC mesh)"
            )
        if self.quantized:
            if wire_error is None:
                raise ValueError(
                    "int8 factor wire needs the error-feedback residuals: "
                    "flush(facs, wire_error=state['wire_error'], seed=step)"
                )
            fn = partial(
                compat.shard_map,
                mesh=self.mesh,
                in_specs=(P(), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )(self._merge_quantized)
            step = jnp.asarray(0 if seed is None else seed, jnp.int32)
            return fn(facs, wire_error, step)
        fn = partial(
            compat.shard_map,
            mesh=self.mesh,
            in_specs=(P(),),
            out_specs=P(),
            check_vma=False,
        )(lambda tree: self.allreduce(tree, self.axis_name))
        return fn(facs)

    def scatter_merge(
        self,
        payload: Dict[str, Dict[str, jnp.ndarray]],
        shard: Dict[str, jnp.ndarray],
        plan,
        decay: jnp.ndarray,
    ) -> Dict[str, jnp.ndarray]:
        """Reduce-scatter per-replica statistics onto the factor shards.

        The owner-sharded replacement for the bucketed allreduce: each
        layer's merged statistic lands ONLY on its eigen-owner's shard row,
        so the wire and the master-EMA memory are both O(model/devices)
        (DP-KFAC, arxiv 2206.15143). ``payload`` is the per-replica local
        statistic tree — ``(1−α)·contribʳ`` for the every-step cadence, or
        the deferred local accumulator at a flush — physically diverged
        across devices; ``shard`` is the ``{"n<size>": [world·rows, n, n]}``
        sharded stack from the KFAC state. The merge is

            shardₙₑw = decay ⊙ shard + mean_r(payload_r)   (owner rows)

        with ``decay`` the traced EMA carry weight (``α``, or ``α^m`` after
        ``m`` deferred capture steps — exact vs. the replicated path by EMA
        linearity). Pad rows of under-loaded devices receive a zero payload
        and just decay; they are never read. Buckets follow
        ``plan.wire_buckets`` (one reduce-scatter per bucket, pinned by
        ``scripts/check_collective_count.py``) and the optional wire
        downcast applies to the bucket payload only, like :meth:`allreduce`.
        """
        axis = self.axis_name
        world = plan.world
        wire_dtype = None if self.comm_dtype == _F32 else self.comm_dtype
        wire = (
            sum(b.size for b in plan.wire_buckets)
            * world
            * self.comm_dtype.itemsize
        )
        tel = get_telemetry()
        tel.set_gauge("kfac/factor_wire_bytes", wire)
        tel.set_gauge("kfac/factor_collectives", len(plan.wire_buckets))
        self.last_wire_bytes = wire
        self.last_collectives = len(plan.wire_buckets)

        # wire-group order (matrix stacks then diagonal-A vector stacks) —
        # FactorBucketEntry.index indexes this list
        wgroups = plan.wire_groups()

        def _body(payload, shard, decay):
            groups: Dict[str, jnp.ndarray] = {}
            for key, n, rows, elems in wgroups:
                flat = jnp.zeros((world * rows, elems), jnp.float32)
                for s in plan.group_slots(n, diag=key.startswith("v")):
                    leaf = payload[s.name][s.factor].astype(jnp.float32)
                    flat = flat.at[s.owner * rows + s.row].set(
                        leaf.reshape(-1)
                    )
                groups[key] = flat.reshape(world, rows * elems)
            new_shard = dict(shard)
            with phase("kfac_exchange", "trace/kfac/factor_comm"):
                for bucket in plan.wire_buckets:
                    parts = [
                        groups[wgroups[e.index][0]] for e in bucket.entries
                    ]
                    buf = (
                        parts[0]
                        if len(parts) == 1
                        else jnp.concatenate(parts, axis=1)
                    )
                    if wire_dtype is not None:
                        buf = buf.astype(wire_dtype)
                    red = lax.psum_scatter(
                        buf, axis, scatter_dimension=0, tiled=True
                    )
                    red = red[0].astype(jnp.float32) / world
                    for e in bucket.entries:
                        key, n, rows, _ = wgroups[e.index]
                        seg = red[e.offset : e.offset + e.size]
                        shape = (rows, n) if key.startswith("v") else (
                            rows, n, n
                        )
                        new_shard[key] = decay * shard[key] + seg.reshape(
                            shape
                        )
            return new_shard

        shard_specs = {k: P(self.axis_name) for k in shard}
        fn = partial(
            compat.shard_map,
            mesh=self.mesh,
            in_specs=(P(), shard_specs, P()),
            out_specs=shard_specs,
            check_vma=False,
        )(_body)
        return fn(payload, shard, decay)
