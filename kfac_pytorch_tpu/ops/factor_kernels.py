"""Fused Pallas patch-covariance kernels: conv A factors without im2col.

``ops/factors.py::compute_a_conv`` materializes the full im2col tensor
``[B, OH, OW, C·kh·kw]`` before its covariance matmul — at batch 128 a
ResNet-50 stage-1 conv (56×56, C·kh·kw = 576) that temporary is ~925 MB of
f32, and every 3×3 conv pays ~kh·kw× its activation footprint in HBM
writes+reads on each factor-update step (docs/PERF.md "Factor-statistics
memory"). The kernels here compute the *same* covariance

    A = PᵀP / (B · spatial²)        (bias column fused, oracle scaling)

directly from the padded NHWC activations: each grid step holds one batch
block of the image in VMEM, slices the ``(i, j)``-shifted strided window out
of it (a reshape-subsample — no extra HBM traffic), and accumulates one
``[TC, TC]`` MXU contraction into an f32 VMEM accumulator that covers every
offset pair of a channel-tile pair. The patch tensor never exists anywhere;
activations are read ~``nc`` times instead of written+read ``kh·kw`` times.

Layout: the kernel accumulates in offset-major order (the natural order of
shifted tiles); a static O(F²) gather permutes the result to the oracle's
channel-major ``(c, kh, kw)`` feature order, so outputs are interchangeable
with ``compute_a_conv`` — the dense path stays untouched as the parity
oracle (tests/test_factor_kernels.py).

``interpret=True`` (automatic on the CPU backend) runs the kernel through the Pallas
interpreter — a lax.scan over the grid, still never materializing im2col —
which is how CPU tier-1 validates the kernel math, same contract as
``ops/flash_attention.py``.

Dispatch: layers call :func:`dispatch_compute_a_conv` /
:func:`dispatch_compute_a_conv_grouped`, which route on the ambient
:func:`factor_kernel_scope` ("dense" unless a train step opened a "pallas"
scope from ``KFAC(factor_kernel=...)``) and record the choice in telemetry
(``kfac/factor_kernel`` gauge, ``trace/kfac/factor_kernel`` span).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kfac_pytorch_tpu import compat
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.observability.telemetry import get_telemetry
from kfac_pytorch_tpu.ops import factors

Padding = Union[str, Sequence[Tuple[int, int]]]

FACTOR_KERNELS = ("auto", "pallas", "dense")

# VMEM budgets (f32 elements). The accumulator covers ALL offset pairs of a
# channel-tile pair — (kh·kw·TC)² — so the channel tile shrinks as the
# window grows; the batch block covers the whole padded image per step.
_ACC_SIDE_LIMIT = 1024  # (kh·kw·TC) ≤ this → accumulator ≤ 4 MB f32
_IMG_BLOCK_ELEMS = 768 * 1024  # per-input image block ≤ 3 MB f32


# ---------------------------------------------------------------------------
# Kernel-selection scope
# ---------------------------------------------------------------------------

_ACTIVE_KERNEL = "dense"


def resolve_factor_kernel(kind: str) -> str:
    """``auto`` → dense on every backend; validate explicit kinds.

    The Pallas capture kernels are refused by the v5e compiler at ResNet-50
    shapes (docs/PERF.md, "Refused by the v5e compiler"), so they are
    opt-in: an explicit ``"pallas"`` compiles or raises the compiler's own
    error — nothing catches it and nothing gives way to dense.
    """
    if kind not in FACTOR_KERNELS:
        raise ValueError(
            f"Invalid factor_kernel: {kind!r} (choose from {FACTOR_KERNELS})"
        )
    return "dense" if kind == "auto" else kind


def active_factor_kernel() -> str:
    """The kernel kind dispatchers currently route to ("pallas"/"dense")."""
    return _ACTIVE_KERNEL


@contextlib.contextmanager
def factor_kernel_scope(kind: str):
    """Route :func:`dispatch_compute_a_conv` inside the block.

    Train steps open this around their capture forward at TRACE time (the
    body of a jitted function runs as Python during tracing), so the flax
    layers — which own the patch-extraction config — pick the kernel the
    ``KFAC(factor_kernel=...)`` config asked for without any layer API
    change. Scopes nest; shape-only discovery (capture.py) pins "dense".
    """
    global _ACTIVE_KERNEL
    prev = _ACTIVE_KERNEL
    _ACTIVE_KERNEL = resolve_factor_kernel(kind)
    try:
        yield
    finally:
        _ACTIVE_KERNEL = prev


# ---------------------------------------------------------------------------
# Geometry
# ---------------------------------------------------------------------------


def _resolve_padding(
    h: int,
    w: int,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    dilation: Tuple[int, int],
):
    """Explicit pad pairs + output spatial dims, XLA conv semantics.

    SAME matches ``lax.padtype_to_pads``: out = ceil(in/stride), total pad =
    max((out-1)·stride + effective_window - in, 0), split low-heavy on the
    high side — the same resolution ``conv_general_dilated_patches`` applies,
    so the fused path sees the identical window grid as the oracle.
    """
    eff = tuple((k - 1) * d + 1 for k, d in zip(kernel_size, dilation))
    if isinstance(padding, str):
        pt = padding.upper()
        if pt == "VALID":
            pads = ((0, 0), (0, 0))
        elif pt == "SAME":
            pads = []
            for size, k_eff, s in zip((h, w), eff, strides):
                out = -(-size // s)
                total = max((out - 1) * s + k_eff - size, 0)
                pads.append((total // 2, total - total // 2))
            pads = tuple(pads)
        else:
            raise ValueError(f"unsupported padding string: {padding!r}")
    else:
        pads = factors._as_pairs(padding)
    oh = (h + pads[0][0] + pads[0][1] - eff[0]) // strides[0] + 1
    ow = (w + pads[1][0] + pads[1][1] - eff[1]) // strides[1] + 1
    if oh <= 0 or ow <= 0:
        raise ValueError(
            f"empty conv output for input {(h, w)} with kernel={kernel_size} "
            f"strides={strides} padding={pads} dilation={dilation}"
        )
    return pads, oh, ow


def _divisor_at_most(n: int, limit: int) -> int:
    for d in range(min(n, max(limit, 1)), 0, -1):
        if n % d == 0:
            return d
    return 1


def _tile_plan(b: int, c: int, kk: int, hpe: int, wpe: int) -> Tuple[int, int]:
    """Pick (batch block, channel tile) — both exact divisors, so the padded
    input needs no batch/channel padding and every block is fully valid."""
    tc = _divisor_at_most(c, max(_ACC_SIDE_LIMIT // kk, 1))
    bb = _divisor_at_most(b, max(_IMG_BLOCK_ELEMS // (hpe * wpe * tc), 1))
    return bb, tc


# ---------------------------------------------------------------------------
# The Pallas kernel
# ---------------------------------------------------------------------------


def _patch_cov_kernel(
    x1_ref, x2_ref, out_ref, acc_ref, *, kw, sh, sw, dh, dw, oh, ow, kk, bb, tc
):
    """One grid step: accumulate PᵀP for one (offset, offset) pair.

    Grid = (nc, nc, nb, kk, kk). The two input blocks are the SAME padded
    image batch block at two channel tiles; they stay VMEM-resident across
    the whole inner (b, o1, o2) sweep (their index maps ignore those grid
    dims). The accumulator spans every offset pair of the channel-tile pair
    and flushes to the output block exactly once, at the sweep's last step.
    """
    b = pl.program_id(2)
    o1 = pl.program_id(3)
    o2 = pl.program_id(4)
    nb = pl.num_programs(2)

    @pl.when((b == 0) & (o1 == 0) & (o2 == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def shifted(ref, o):
        # The (i, j)-shifted strided window of the padded image, entirely in
        # VMEM: slice rows [i·dh, i·dh + sh·oh) then keep every sh-th via a
        # reshape-subsample (static strides; dynamic start from program_id).
        i, j = o // kw, o % kw
        v = ref[:, pl.ds(i * dh, sh * oh), pl.ds(j * dw, sw * ow), :]
        v = v.reshape(bb, oh, sh, ow, sw, tc)[:, :, 0, :, 0, :]
        return v.reshape(bb * oh * ow, tc)

    prod = jax.lax.dot_general(
        shifted(x1_ref, o1),
        shifted(x2_ref, o2),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    cur = acc_ref[pl.ds(o1 * tc, tc), pl.ds(o2 * tc, tc)]
    acc_ref[pl.ds(o1 * tc, tc), pl.ds(o2 * tc, tc)] = cur + prod

    @pl.when((b == nb - 1) & (o1 == kk - 1) & (o2 == kk - 1))
    def _flush():
        out_ref[...] = acc_ref[...]


def _patch_cov_pallas(
    xp: jnp.ndarray,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    dilation: Tuple[int, int],
    oh: int,
    ow: int,
    bb: int,
    tc: int,
    interpret: bool,
) -> jnp.ndarray:
    """Raw patch second-moment sums ``Σ_rows P'ᵀP'`` in INTERNAL layout.

    ``xp``: padded f32 activations ``[B, HPE, WPE, C]`` with ``bb | B`` and
    ``tc | C``. Internal feature index = ``c_tile·(kk·tc) + o·tc + c_in_tile``
    (offset-major within a channel tile); callers permute to channel-major.
    """
    b, hpe, wpe, c = xp.shape
    kh, kwid = kernel_size
    kk = kh * kwid
    nb, nc = b // bb, c // tc
    side = kk * tc

    kernel = functools.partial(
        _patch_cov_kernel,
        kw=kwid,
        sh=strides[0],
        sw=strides[1],
        dh=dilation[0],
        dw=dilation[1],
        oh=oh,
        ow=ow,
        kk=kk,
        bb=bb,
        tc=tc,
    )
    return pl.pallas_call(
        kernel,
        grid=(nc, nc, nb, kk, kk),
        in_specs=[
            pl.BlockSpec(
                (bb, hpe, wpe, tc), lambda c1, c2, nbi, o1, o2: (nbi, 0, 0, c1)
            ),
            pl.BlockSpec(
                (bb, hpe, wpe, tc), lambda c1, c2, nbi, o1, o2: (nbi, 0, 0, c2)
            ),
        ],
        out_specs=pl.BlockSpec(
            (side, side), lambda c1, c2, nbi, o1, o2: (c1, c2)
        ),
        out_shape=jax.ShapeDtypeStruct((nc * side, nc * side), jnp.float32),
        scratch_shapes=[pltpu.VMEM((side, side), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=(
                "parallel",
                "parallel",
                "arbitrary",
                "arbitrary",
                "arbitrary",
            ),
        ),
        interpret=interpret,
    )(xp, xp)


def _channel_major_perm(c: int, kk: int, tc: int) -> np.ndarray:
    """Gather indices: internal (c_tile, offset, c_in_tile) → oracle (c, o)."""
    ci = np.arange(c)[:, None]
    o = np.arange(kk)[None, :]
    return ((ci // tc) * (kk * tc) + o * tc + (ci % tc)).reshape(-1)


def _default_interpret(interpret: Optional[bool]) -> bool:
    """Compile on a TPU, interpret on the CPU backend the tests force,
    refuse any other backend (there is no Pallas-TPU lowering for it)."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas-TPU kernels compile on a TPU and interpret on the CPU "
            f"backend only; default backend is {backend!r}"
        )
    return backend == "cpu"


def compute_a_conv_fused(
    a: jnp.ndarray,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    has_bias: bool,
    kernel_dilation: Tuple[int, int] = (1, 1),
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Drop-in for ``factors.compute_a_conv`` minus the im2col temporary.

    Same result up to f32 summation order: the oracle divides the patch
    matrix by ``spatial`` before one big matmul; the kernel accumulates raw
    products per (batch-block, offset-pair) tile and applies the fused
    ``1/(spatial²·B)`` once at the end. The bias column (entries
    ``1/spatial``, appended before the division — oracle semantics) reduces
    on the batch-collapsed image, so it costs O(H·W·C), not O(B·H·W·C·kh·kw).
    """
    kernel_size = tuple(kernel_size)
    strides = tuple(strides)
    kernel_dilation = tuple(kernel_dilation)
    b, h, w, c = a.shape
    pads, oh, ow = _resolve_padding(
        h, w, kernel_size, strides, padding, kernel_dilation
    )
    kh, kwid = kernel_size
    kk = kh * kwid
    dh, dw = kernel_dilation
    sh, sw = strides
    # Padded extents sized for the kernel's slice+subsample (always ≥ the
    # conv's natural padded size; extra bottom/right zeros are never selected
    # by the stride subsample, so they do not perturb the sums).
    hpe = (kh - 1) * dh + sh * oh
    wpe = (kwid - 1) * dw + sw * ow
    x = a.astype(jnp.float32)
    xp = jnp.pad(
        x,
        (
            (0, 0),
            (pads[0][0], hpe - h - pads[0][0]),
            (pads[1][0], wpe - w - pads[1][0]),
            (0, 0),
        ),
    )
    bb, tc = _tile_plan(b, c, kk, hpe, wpe)
    raw = _patch_cov_pallas(
        xp, kernel_size, strides, kernel_dilation, oh, ow, bb, tc,
        _default_interpret(interpret),
    )
    perm = _channel_major_perm(c, kk, tc)
    spatial = oh * ow
    scale = 1.0 / (float(spatial) ** 2 * float(b))
    feat = raw[perm][:, perm] * scale
    if not has_bias:
        return feat
    # Bias cross terms: column sums of P, computed on the batch-reduced
    # padded image (the only O(B·H·W·C) pass) via kh·kw static shifted sums.
    xs = jnp.sum(xp, axis=0)  # [HPE, WPE, C]
    cols = [
        jnp.sum(
            xs[
                i * dh : i * dh + (oh - 1) * sh + 1 : sh,
                j * dw : j * dw + (ow - 1) * sw + 1 : sw,
                :,
            ],
            axis=(0, 1),
        )
        for i in range(kh)
        for j in range(kwid)
    ]
    col = jnp.stack(cols, axis=-1).reshape(-1) * scale  # channel-major [F]
    corner = jnp.full((1,), 1.0 / spatial, jnp.float32)
    top = jnp.concatenate([feat, col[:, None]], axis=1)
    bot = jnp.concatenate([col, corner])[None, :]
    return jnp.concatenate([top, bot], axis=0)


def compute_a_conv_grouped_fused(
    a: jnp.ndarray,
    groups: int,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    has_bias: bool,
    kernel_dilation: Tuple[int, int] = (1, 1),
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Stacked per-group fused A factors: ``[G, a, a]``.

    Per-group accumulators: each group's channel slice gets its own kernel
    invocation (own VMEM accumulator), exactly mirroring the dense path's
    vmap over per-group :func:`factors.compute_a_conv` — cross-group
    covariance blocks are never computed, so the fused grouped path does
    ``1/G`` of the full kernel's work, like the oracle.
    """
    b, h, w, c = a.shape
    cg = c // groups
    return jnp.stack(
        [
            compute_a_conv_fused(
                jax.lax.slice_in_dim(a, g * cg, (g + 1) * cg, axis=3),
                kernel_size,
                strides,
                padding,
                has_bias,
                kernel_dilation,
                interpret=interpret,
            )
            for g in range(groups)
        ],
        axis=0,
    )


# ---------------------------------------------------------------------------
# Token-gather covariance: embedding diagonal-A statistics in O(B·T)
# ---------------------------------------------------------------------------

# Token block per grid step (ids are tiny; this bounds the [TB, TV] one-hot
# compare tile, the only "one-hot" that ever exists — in VMEM, never HBM).
_TOK_BLOCK = 1024
# Vocab tile (lane-dim multiple); the output counts block per grid step.
_VOCAB_TILE = 512


def _token_count_kernel(ids_ref, out_ref, *, tb, tv):
    """One grid step: bincount one token block against one vocab tile.

    Grid = (nv, nb). The output block (one vocab tile of the counts row)
    stays VMEM-resident across the whole token sweep b = 0..nb-1 (its index
    map ignores b): zero at the first block, accumulate a [TB, TV] one-hot
    compare-reduce each step. Padded ids carry a sentinel ≥ the padded vocab,
    so they match no tile and contribute nothing.
    """
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[0, :]  # [TB] int32
    base = pl.program_id(0) * tv
    # 2-D iota (1-D iota fails on TPU): absolute vocab ids for this tile.
    tile_ids = base + jax.lax.broadcasted_iota(jnp.int32, (tb, tv), 1)
    hits = (ids[:, None] == tile_ids).astype(jnp.float32)
    out_ref[...] += jnp.sum(hits, axis=0, keepdims=True)


def compute_a_embed_fused(
    ids: jnp.ndarray,
    vocab: int,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Drop-in for ``factors.compute_a_embed`` as a streamed Pallas bincount.

    The [B·T, V] one-hot and the dense [V, V] A factor never exist: the grid
    streams token blocks through VMEM, each step comparing one [TB] id block
    against one vocab tile's iota and accumulating the [1, TV] hit counts in
    the resident output block — O(B·T) work and O(B·T + V) memory. Counts
    are integers in f32, so dividing by N afterwards reproduces the
    scatter-add oracle bitwise.
    """
    flat = ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    tb = min(_TOK_BLOCK, max(_divisor_at_most(n, _TOK_BLOCK), 1))
    vp = -(-vocab // _VOCAB_TILE) * _VOCAB_TILE
    nv = vp // _VOCAB_TILE
    npad = -(-n // tb) * tb
    # Sentinel = padded vocab: beyond every tile's iota, so padding rows are
    # inert (and even slot `vocab`, discarded by the final slice, stays 0).
    flat = jnp.pad(flat, (0, npad - n), constant_values=vp)
    blocks = flat.reshape(npad // tb, tb)
    nb = blocks.shape[0]

    kernel = functools.partial(_token_count_kernel, tb=tb, tv=_VOCAB_TILE)
    counts = pl.pallas_call(
        kernel,
        grid=(nv, nb),
        in_specs=[pl.BlockSpec((1, tb), lambda v, b: (b, 0))],
        out_specs=pl.BlockSpec((1, _VOCAB_TILE), lambda v, b: (0, v)),
        out_shape=jax.ShapeDtypeStruct((1, vp), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_default_interpret(interpret),
    )(blocks)
    return counts.reshape(-1)[:vocab] / n


# ---------------------------------------------------------------------------
# Dispatch (called from models/layers.py at capture-trace time)
# ---------------------------------------------------------------------------


def dispatch_compute_a_conv(
    a: jnp.ndarray,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    has_bias: bool,
    kernel_dilation: Tuple[int, int] = (1, 1),
) -> jnp.ndarray:
    """Route one conv layer's A contribution per the ambient kernel scope."""
    tel = get_telemetry()
    kind = active_factor_kernel()
    tel.set_gauge("kfac/factor_kernel", 1.0 if kind == "pallas" else 0.0)
    with phase("kfac_capture", "trace/kfac/factor_kernel"):
        if kind == "pallas":
            # A is a statistics by-product, never differentiated — cut the
            # tangent path so autodiff of the capture forward does not need
            # a pallas_call JVP rule.
            return compute_a_conv_fused(
                jax.lax.stop_gradient(a),
                kernel_size,
                strides,
                padding,
                has_bias,
                kernel_dilation=kernel_dilation,
            )
        return factors.compute_a_conv(
            a,
            kernel_size,
            strides,
            padding,
            has_bias,
            kernel_dilation=kernel_dilation,
        )


def dispatch_compute_a_embed(ids: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Route an embedding layer's diagonal-A contribution per the scope.

    Token ids are integers — no tangent path exists, so unlike the conv
    dispatchers no ``stop_gradient`` is needed around the pallas path.
    """
    tel = get_telemetry()
    kind = active_factor_kernel()
    tel.set_gauge("kfac/embedding_capture_kernel", 1.0 if kind == "pallas" else 0.0)
    with phase("kfac_capture", "trace/kfac/factor_kernel"):
        if kind == "pallas":
            return compute_a_embed_fused(ids, vocab)
        return factors.compute_a_embed(ids, vocab)


def dispatch_compute_a_moe(
    expert_ids: jnp.ndarray, num_experts: int
) -> jnp.ndarray:
    """Expert token fractions ``counts_e / N`` for an MoE layer, per scope.

    The ``[tokens, experts]`` dispatch one-hot is exactly the embedding
    one-hot with ``vocab = num_experts``, so the MoE fraction vector rides
    the same streamed Pallas bincount (``compute_a_embed_fused``) — the
    one-hot never densifies in HBM on either path. Integer ids: no tangent
    path, no ``stop_gradient`` needed.
    """
    tel = get_telemetry()
    kind = active_factor_kernel()
    tel.set_gauge("kfac/moe_dispatch_kernel", 1.0 if kind == "pallas" else 0.0)
    with phase("kfac_capture", "trace/kfac/factor_kernel"):
        if kind == "pallas":
            return compute_a_embed_fused(expert_ids, num_experts)
        return factors.compute_a_embed(expert_ids, num_experts)


def dispatch_compute_a_conv_grouped(
    a: jnp.ndarray,
    groups: int,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    has_bias: bool,
    kernel_dilation: Tuple[int, int] = (1, 1),
) -> jnp.ndarray:
    """Grouped-conv twin of :func:`dispatch_compute_a_conv`."""
    tel = get_telemetry()
    kind = active_factor_kernel()
    tel.set_gauge("kfac/factor_kernel", 1.0 if kind == "pallas" else 0.0)
    with phase("kfac_capture", "trace/kfac/factor_kernel"):
        if kind == "pallas":
            return compute_a_conv_grouped_fused(
                jax.lax.stop_gradient(a),
                groups,
                kernel_size,
                strides,
                padding,
                has_bias,
                kernel_dilation=kernel_dilation,
            )
        return factors.compute_a_conv_grouped(
            a,
            groups,
            kernel_size,
            strides,
            padding,
            has_bias,
            kernel_dilation=kernel_dilation,
        )
