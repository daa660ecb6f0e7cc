"""Pallas TPU flash attention: fused blockwise softmax-attention kernel.

The hot op of the transformer path (models/transformer_lm.py,
models/glm_moe_lite.py). XLA's naive attention materializes the [B, H, T, T]
logits in HBM; this kernel forms them a tile at a time in VMEM with the
online-softmax recurrence (running max m, normalizer l, f32 accumulator), so
HBM traffic is O(T·D) per head and the two matmuls per tile ride the MXU.
Same recurrence as the cross-device ring fold (parallel/context.py) — this is
the within-chip tier of the same algorithm.

The grid (PR 29). Forward and dq run ``(batch·head, T/block_q, T/major)``;
dk/dv runs ``(batch·head, T/block_k, T/major)``. ``major`` is how much of the
*swept* operand (K and V; for dk/dv Q, dO, lse and Δ) a grid step holds in
VMEM: the whole sequence where that fits the budget, so the third axis has one
step, its index map ignores the second, and Pallas fetches a head's K and V
once, not once per q block. Inside the step a ``lax.fori_loop`` walks
sub-blocks of ``block_k`` keys (``block_q`` queries for dk/dv) of the held
operand with ``pl.ds``, bounded by the causal limit: a tile above the
diagonal costs no fetch, no grid step and no branch, and only the tiles the
diagonal crosses pay the mask. Where a head does not fit (long sequences),
``major`` is the widest k-major block that does; the carry then lives in
scratch across the third axis and the index map is clamped to the last live
block, so dead steps re-use what is resident. ``_choose_blocks`` picks all of
it from ``t``, ``d`` and the operands' item size, and sets the call's
``vmem_limit_bytes`` from the same sum; explicit ``block_q`` / ``block_k``
keep their meaning (the tile of logits one loop step forms). Before PR 29
every 128 x 128 tile was a grid step of ``(batch·head, T/128, T/128)``: 6,144
steps a call at GPT-2's shape, nearly all of its 3.5 ms their fixed cost
(PERF.md section 6, PR 29).

Arithmetic: operands upcast to float32 in the kernel, ``dot_general`` with
the precision unset and a float32 result, statistics and accumulators in
float32 (what Mosaic gives float32 operands at that setting: PERF.md section
6, PR 29).

Training is fully fused too: the backward is two blockwise Pallas kernels
(dq; dk/dv) that recompute attention probabilities per tile from the saved
logsumexp — residual memory is O(T·D) (q, k, v, out, lse), never O(T²), in
both directions.

Drop-in for ``parallel.context.full_attention`` (signature
``(q, k, v, causal=...) -> out`` on [B, T, H, D]); auto-selected on TPU by
``best_attention_fn()``. ``interpret=True`` runs the kernels in the Pallas
interpreter (CPU) — that's how tests validate the math without TPU hardware;
``tests/test_flash_attention.py::test_tpu_hardware_*`` runs them through
Mosaic on a real chip. Trace-time gauges ``attention/flash_*`` (calls, grid
steps, resident calls, live share of the logits): docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kfac_pytorch_tpu import compat
from kfac_pytorch_tpu.observability.telemetry import get_telemetry

_NEG_INF = -1e30
_LANES = 128  # TPU lane width: minor dim of the lane-replicated row stats
_BLOCKS = (512, 256, 128)  # block sizes tried, widest first (scripts/flash_sweep.py)
_VMEM_DEFAULT = 16 * 2**20  # what Mosaic grants a kernel that asks for nothing
_VMEM_BUDGET = 32 * 2**20  # the most a kernel's blocks and tiles may sum to
logger = logging.getLogger(__name__)
_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    """Log a path-selection decision once per process — a 'flash' benchmark
    must not silently measure the naive kernel (round-2 verdict, weak #7)."""
    if key not in _warned:
        _warned.add(key)
        logger.warning(msg)


class _Tiling(NamedTuple):
    """How the three kernels cut a head of ``t`` positions (hashable: a jit
    static). ``block_q`` query rows and ``block_k`` keys make one tile of
    logits; ``major`` is how many positions of the *swept* operand a grid step
    holds in VMEM (keys and values for forward and dq, queries and dO for
    dk/dv): ``t`` when a head's are resident, a smaller multiple of both
    blocks when they do not fit."""

    block_q: int
    block_k: int
    major: int
    vmem_limit: int


def _vmem_bytes(block_q: int, block_k: int, major: int, d: int, itemsize: int) -> int:
    """VMEM the largest of the three kernels asks for: pipelined blocks twice
    (double-buffered), scratch once, the float32 tiles of one loop step."""
    f32 = 4
    stats = _LANES * f32  # a lane-replicated row of lse / delta / m / l
    sweep_q = 2 * (3 * block_q * d * itemsize + 2 * block_q * stats)  # q, dO, dq|o, lse, delta
    held_kv = 2 * 2 * major * d * itemsize
    fwd_dq = sweep_q + held_kv + block_q * d * f32 + 2 * block_q * stats
    sweep_k = 2 * 4 * block_k * d * itemsize  # k, v, dk, dv
    held_q = 2 * (2 * major * d * itemsize + 2 * major * stats)  # q, dO, lse, delta
    dkv = sweep_k + held_q + 2 * block_k * d * f32
    tiles = 6 * block_q * block_k * f32 + 2 * (block_q + block_k) * d * f32
    return max(fwd_dq, dkv) + tiles


def _choose_blocks(
    t: int,
    d: int,
    itemsize: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    budget: int = _VMEM_BUDGET,
) -> Optional[_Tiling]:
    """The tiling for heads of ``d`` over ``t`` positions, from what the code
    can see; ``None`` where no block divides ``t`` (the caller's exact path).

    A block not given is the widest of ``_BLOCKS`` that divides ``t``, the key
    block no wider than the query block (a key block wider than the query
    block computes dead columns the mask then throws away). The swept operand
    is held whole (``major == t``) where the kernels' VMEM sum stays under
    ``budget``, else in the widest k-major (q-major for dk/dv) block that
    does; ``vmem_limit`` is that sum with half again for what Mosaic keeps
    beside it, never under the 16 MiB the compiler grants unasked."""
    if block_q is None:
        block_q = next((b for b in _BLOCKS if t % b == 0), None)
    if block_k is None:
        block_k = next((b for b in _BLOCKS if t % b == 0 and b <= (block_q or 0)), None)
    if not block_q or not block_k or t % block_q or t % block_k:
        return None
    step = math.lcm(block_q, block_k)
    majors = [m for m in range(t, 0, -step) if t % m == 0]  # multiples of both blocks, t first
    major = next(
        (m for m in majors if _vmem_bytes(block_q, block_k, m, d, itemsize) <= budget),
        majors[-1],
    )
    need = _vmem_bytes(block_q, block_k, major, d, itemsize)
    return _Tiling(block_q, block_k, major, max(_VMEM_DEFAULT, need + need // 2))


# Trace-time counts of the kernel calls built since the last
# :func:`reset_flash_tally` (the precedent is ops/factors.py::_TALLY).
_TALLY = {"calls": 0, "grid_steps": 0, "kv_resident": 0, "tiles": 0, "full": 0}


def reset_flash_tally() -> None:
    """Start the counts behind the four ``attention/flash_*`` gauges anew. The
    step builders call it where a step program's forward/backward starts to
    trace, so the gauges describe that program."""
    _TALLY.update(calls=0, grid_steps=0, kv_resident=0, tiles=0, full=0)


def _live_tiles(t: int, tiling: _Tiling, causal: bool) -> int:
    """Logit elements one head's kernel call computes: whole tiles up to and
    including those the diagonal crosses."""
    bq, bk = tiling.block_q, tiling.block_k
    if not causal:
        return t * t
    return sum(-(-(qi + 1) * bq // bk) * bk * bq for qi in range(t // bq))


def _tally(grid: Tuple[int, int, int], t: int, tiling: _Tiling, causal: bool) -> None:
    """Count one kernel call of ``grid`` (``grid[0]`` heads) into the gauges."""
    _TALLY["calls"] += 1
    _TALLY["grid_steps"] += math.prod(grid)
    _TALLY["kv_resident"] += tiling.major == t
    _TALLY["tiles"] += grid[0] * _live_tiles(t, tiling, causal)
    _TALLY["full"] += grid[0] * t * t
    tel = get_telemetry()
    tel.set_gauge("attention/flash_calls", _TALLY["calls"])
    tel.set_gauge("attention/flash_grid_steps", _TALLY["grid_steps"])
    tel.set_gauge("attention/flash_kv_resident", _TALLY["kv_resident"])
    tel.set_gauge("attention/flash_live_share", _TALLY["tiles"] / _TALLY["full"])


def _grid(bh: int, t: int, tiling: _Tiling, sweep_block: int) -> Tuple[int, int, int]:
    return (bh, t // sweep_block, t // tiling.major)


def _across(stat, width: int):
    """A lane-replicated row statistic ``[rows, _LANES]`` (m, l, lse, Δ: every
    lane of a row the same value) as ``[rows, width]``: whole copies of its
    vregs side by side, or their first lanes, where ``width`` allows it (a
    ``[:, :1]`` slice broadcast over the lanes costs a lane shuffle a vreg)."""
    if width % _LANES == 0:
        return jnp.tile(stat, (1, width // _LANES))
    if width < _LANES:
        return stat[:, :width]
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))


def _logits(q, kb, q0, k0, masked: bool):
    """One tile of scaled logits, float32; ``masked`` tiles (those the
    diagonal crosses) get the causal ``where``, the others need none."""
    logits = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [block_q, block_k]
    if masked:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(q_pos >= k_pos, logits, _NEG_INF)
    return logits


def _p_and_ds(q, kb, vb, do, lse, delta, q0, k0, masked: bool):
    """What both backward kernels recompute per tile: P from the saved lse and
    dS = P ⊙ (dO·Vᵀ − Δ), ``[block_q, block_k]`` each."""
    p = jnp.exp(_logits(q, kb, q0, k0, masked) - lse)
    dp = jax.lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return p, p * (dp - delta)


def _key_ranges(qi, kk, block_q: int, block_k: int, major: int, causal: bool):
    """Key sub-blocks of the k-major block ``kk`` that query block ``qi``
    multiplies, as two half-open ranges of global sub-block indices: those
    wholly at or under the diagonal (``clear``: no mask), then those it
    crosses. Dead sub-blocks are in neither: the loops do not step over them."""
    per = major // block_k
    lo = kk * per
    hi = lo + per
    if not causal:
        return (lo, hi), (hi, hi)
    clear = (qi * block_q + 1) // block_k
    live = ((qi + 1) * block_q + block_k - 1) // block_k
    return (lo, jnp.minimum(hi, clear)), (jnp.maximum(lo, clear), jnp.minimum(hi, live))


def _query_ranges(kj, qq, block_q: int, block_k: int, major: int, causal: bool):
    """The same for dk/dv: query sub-blocks of the q-major block ``qq`` that
    key block ``kj`` meets: those wholly under the diagonal, then those it
    crosses, from the first it reaches. Sub-blocks above it are in neither."""
    per = major // block_q
    lo = qq * per
    hi = lo + per
    if not causal:
        return (lo, hi), (hi, hi)
    first = (kj * block_k) // block_q
    clear = ((kj + 1) * block_k + block_q - 2) // block_q
    return (jnp.maximum(lo, clear), hi), (jnp.maximum(lo, first), jnp.minimum(hi, clear))


def _sweep(clear, crossed, body) -> None:
    """Run ``body(index, masked)`` over the two ranges of a ``_*_ranges``
    result: ``lax.fori_loop``s with the causal limit as their bound (an empty
    range costs no step), not a Python unroll: a program holds 36 of these
    kernels and device code counts in ``peak_hbm_gib`` byte for byte."""
    for (lo, hi), masked in ((clear, False), (crossed, True)):

        def step(i, carry, masked=masked):
            body(i, masked)
            return carry

        jax.lax.fori_loop(lo, hi, step, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                causal: bool, scale: float, block_k: int):
    """One (batch·head, q-block, k-major block) forward program.

    The head's keys are swept INSIDE the grid step: a ``fori_loop`` over
    sub-blocks of ``block_k`` keys of the resident k-major block (``pl.ds``),
    bounded by the causal limit, with the online-softmax carry in VMEM
    scratch. Where K and V are resident per head the third grid axis has one
    step; where they are not, the carry lives across its steps and the index
    map holds the last live block through the dead ones. Refs (leading
    singleton = batch·head): q/o [1, block_q, D]; k/v [1, major, D];
    lse [1, block_q, _LANES] (logsumexp of the scaled logits, the backward
    residual, replicated across the 128-lane minor dim — Mosaic requires the
    last two block dims be (8k, 128m) or whole-array, so a [1, block_q]
    per-row vector is unlowerable; lane-replicating is the standard layout,
    cf. jax's own pallas.ops.tpu.flash_attention which stores l/m the same
    way. The interpreter accepts either, which is why this only failed the
    first time the kernel met real hardware).
    """
    block_q, major = q_ref.shape[1], k_ref.shape[1]
    qi, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale

    def fold(kj, masked):
        rows = pl.ds(pl.multiple_of(kj * block_k - kk * major, block_k), block_k)
        kb = k_ref[0, rows, :].astype(jnp.float32)
        vb = v_ref[0, rows, :].astype(jnp.float32)
        logits = _logits(q, kb, qi * block_q, kj * block_k, masked)
        m = m_scr[:]  # (block_q, _LANES), lanes identical
        m_new = jnp.maximum(m, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - _across(m_new, block_k))
        corr = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * _across(corr, acc_scr.shape[1]) + jnp.dot(
            p, vb, preferred_element_type=jnp.float32
        )

    _sweep(*_key_ranges(qi, kk, block_q, block_k, major, causal), fold)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / _across(l, acc_scr.shape[1])).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _held_keys(tiling: _Tiling, causal: bool):
    """Index of the k-major block a (q-block ``j``, step ``kk``) holds: ``kk``,
    clamped to the last block the causal limit reaches so that the dead steps
    after it re-use the block already resident (no fetch)."""
    if not causal:
        return lambda i, j, kk: (i, kk, 0)
    return lambda i, j, kk: (
        i, jnp.minimum(kk, ((j + 1) * tiling.block_q - 1) // tiling.major), 0)


def _held_queries(tiling: _Tiling, causal: bool):
    """The same for dk/dv: the q-major block a (k-block ``kk``, step ``j``)
    holds, clamped to the first live one through the dead steps before it."""
    if not causal:
        return lambda i, kk, j: (i, j, 0)
    return lambda i, kk, j: (
        i, jnp.maximum(j, (kk * tiling.block_k) // tiling.major), 0)


def _heads_major(x):
    """[B, T, H, D] -> [B·H, T, D]: the grids' first axis is the head."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _heads_minor(x, b: int):
    """[B·H, T, D] -> [B, T, H, D]."""
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _compiler_params(tiling: _Tiling):
    return compat.tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=tiling.vmem_limit,
    )


@functools.partial(jax.jit, static_argnames=("causal", "tiling", "interpret"))
def _flash_forward(q, k, v, causal, tiling, interpret):
    b, t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    block_q, block_k, major, _ = tiling
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_k=block_k
    )
    held = pl.BlockSpec((1, major, d), _held_keys(tiling, causal))
    out, lse = pl.pallas_call(
        kernel,
        grid=_grid(b * h, t, tiling, block_q),
        in_specs=[pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)), held, held],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda i, j, kk: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(tiling),
        interpret=interpret,
    )(_heads_major(q), _heads_major(k), _heads_major(v))
    return _heads_minor(out, b), lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
                   dq_scr, *, causal: bool, scale: float, block_k: int):
    """dQ program: grid (batch·head, q-block, k-major block), the forward's.

    Per (q-block): recompute p from the saved lse for each live key
    sub-block, fold ``ds @ K`` into a VMEM accumulator. dS = P ⊙ (dO·Vᵀ − Δ)
    with Δ = rowsum(dO ⊙ O) computed outside (one cheap fused elementwise
    pass).
    """
    block_q, major = q_ref.shape[1], k_ref.shape[1]
    qi, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = _across(lse_ref[0], block_k)
    delta = _across(dl_ref[0], block_k)

    def fold(kj, masked):
        rows = pl.ds(pl.multiple_of(kj * block_k - kk * major, block_k), block_k)
        kb = k_ref[0, rows, :].astype(jnp.float32)
        vb = v_ref[0, rows, :].astype(jnp.float32)
        _, ds = _p_and_ds(q, kb, vb, do, lse, delta, qi * block_q, kj * block_k, masked)
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds, kb, preferred_element_type=jnp.float32
        ) * scale

    _sweep(*_key_ranges(qi, kk, block_q, block_k, major, causal), fold)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                    scale: float, block_q: int):
    """dK/dV program: grid (batch·head, k-block, q-major block).

    Per (k-block): fold ``dSᵀ @ (scale·Q)`` and ``Pᵀ @ dO`` over the live
    query sub-blocks of the resident Q, dO, lse and Δ, from the first one the
    diagonal reaches.
    """
    block_k, major = k_ref.shape[1], q_ref.shape[1]
    kj, qq = pl.program_id(1), pl.program_id(2)

    @pl.when(qq == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    kb = k_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)

    def fold(qi, masked):
        rows = pl.ds(pl.multiple_of(qi * block_q - qq * major, block_q), block_q)
        q = q_ref[0, rows, :].astype(jnp.float32) * scale
        do = do_ref[0, rows, :].astype(jnp.float32)
        lse = _across(lse_ref[0, rows, :], block_k)
        delta = _across(dl_ref[0, rows, :], block_k)
        p, ds = _p_and_ds(q, kb, vb, do, lse, delta, qi * block_q, kj * block_k, masked)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _sweep(*_query_ranges(kj, qq, block_q, block_k, major, causal), fold)

    @pl.when(qq == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_dq(qb, kb, vb, dob, lse, delta, causal, tiling, interpret):
    """dQ of [B·H, T, D] operands: the forward's grid and held K and V."""
    bh, t, d = qb.shape
    block_q, block_k, major, _ = tiling
    q_spec = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0))
    r_spec = pl.BlockSpec((1, block_q, _LANES), lambda i, j, kk: (i, j, 0))
    held_kv = pl.BlockSpec((1, major, d), _held_keys(tiling, causal))
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, scale=1.0 / math.sqrt(d), block_k=block_k
        ),
        grid=_grid(bh, t, tiling, block_q),
        in_specs=[q_spec, held_kv, held_kv, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qb.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(tiling),
        interpret=interpret,
    )(qb, kb, vb, dob, lse, delta)


def _flash_dkv(qb, kb, vb, dob, lse, delta, causal, tiling, interpret):
    """dK and dV: grid (heads, k-blocks, q-majors): the queries are the swept
    side, held with dO, lse and Δ."""
    bh, t, d = qb.shape
    block_q, block_k, major, _ = tiling
    k_spec = pl.BlockSpec((1, block_k, d), lambda i, kk, j: (i, kk, 0))
    held = _held_queries(tiling, causal)
    held_q = pl.BlockSpec((1, major, d), held)
    held_r = pl.BlockSpec((1, major, _LANES), held)
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, scale=1.0 / math.sqrt(d), block_q=block_q
        ),
        grid=_grid(bh, t, tiling, block_k),
        in_specs=[held_q, k_spec, k_spec, held_q, held_r, held_r],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), kb.dtype),
            jax.ShapeDtypeStruct((bh, t, d), vb.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(tiling),
        interpret=interpret,
    )(qb, kb, vb, dob, lse, delta)


@functools.partial(jax.jit, static_argnames=("causal", "tiling", "interpret"))
def _flash_backward(q, k, v, out, lse, g, causal, tiling, interpret):
    qb, kb, vb, dob, ob = (_heads_major(x) for x in (q, k, v, g, out))
    # Δ_i = Σ_d dO_id · O_id — one fused elementwise+reduce pass, then
    # lane-replicated to the stats layout (see _fwd_kernel docstring)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    dq = _flash_dq(qb, kb, vb, dob, lse, delta, causal, tiling, interpret)
    dk, dv = _flash_dkv(qb, kb, vb, dob, lse, delta, causal, tiling, interpret)
    return tuple(_heads_minor(x, q.shape[0]) for x in (dq, dk, dv))


def _count(q, causal, tiling, *sweep_blocks):
    """One kernel call per block given: the block its grid's second axis cuts
    ``t`` by (``block_q`` for forward and dq, ``block_k`` for dk/dv)."""
    b, t, h, _ = q.shape
    for block in sweep_blocks:
        _tally(_grid(b * h, t, tiling, block), t, tiling, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, tiling, interpret):
    _count(q, causal, tiling, tiling.block_q)
    return _flash_forward(q, k, v, causal, tiling, interpret)[0]


def _flash_fwd(q, k, v, causal, tiling, interpret):
    _count(q, causal, tiling, tiling.block_q)
    out, lse = _flash_forward(q, k, v, causal, tiling, interpret)
    # Residuals are O(T·D): inputs + output + per-row logsumexp. No [T, T]
    # tensor is ever stored — the backward kernels recompute P per block.
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, tiling, interpret, res, g):
    q, k, v, out, lse = res
    _count(q, causal, tiling, tiling.block_q, tiling.block_k)  # dq, dk/dv
    return _flash_backward(q, k, v, out, lse, g, causal, tiling, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused attention over [B, T, H, D] (layout of the transformer blocks).

    Differentiable with a fused blockwise backward (memory O(T·D) in both
    directions). ``block_q`` / ``block_k`` are the tile of logits one loop
    step forms; left ``None`` they are chosen from ``t``, ``d`` and the
    operands' item size (``_choose_blocks``), as is whether a head's K and V
    stay resident. Falls back to the exact jnp path for sequences no block
    divides — the kernel's win is only at block scale anyway; the fallback
    is logged once so benchmarks cannot silently measure the naive kernel.
    The two jitted halves lower each distinct kernel once a program.
    """
    t, d = q.shape[1], q.shape[3]
    tiling = _choose_blocks(t, d, jnp.dtype(q.dtype).itemsize, block_q, block_k)
    if tiling is None:
        from kfac_pytorch_tpu.parallel import context

        _warn_once(
            f"fallback-{t}-{block_q}-{block_k}",
            f"flash_attention: T={t} not divisible by blocks "
            f"({block_q or _BLOCKS}/{block_k or _BLOCKS}); using exact jnp attention",
        )
        return context.full_attention(q, k, v, causal=causal)
    return _flash(q, k, v, causal, tiling, interpret)


def best_attention_fn(interpret: bool = False):
    """``full_attention``-compatible fn: the Pallas kernel on a SINGLE TPU
    device, exact jnp elsewhere.

    Multi-device jit programs keep the jnp path: a Mosaic custom call has no
    GSPMD partitioning rule, so under pjit it would have to be wrapped in
    shard_map per mesh — the sequence-parallel tier (parallel/context.py)
    covers that case instead. The choice is logged once.
    """
    single_tpu = jax.devices()[0].platform == "tpu" and jax.device_count() == 1
    if single_tpu or interpret:
        _warn_once(
            "path-flash",
            "best_attention_fn: using fused Pallas flash attention"
            + (" (interpreter)" if interpret else ""),
        )
        return functools.partial(flash_attention, interpret=interpret)
    from kfac_pytorch_tpu.parallel import context

    _warn_once(
        "path-exact",
        f"best_attention_fn: using exact jnp attention "
        f"(platform={jax.devices()[0].platform}, "
        f"devices={jax.device_count()})",
    )
    return context.full_attention
