"""Kronecker factor statistics (A = input covariance, G = grad-output covariance).

Behavioral parity with the reference factor math (kfac/utils.py:56-183):

* ``compute_a_dense`` / ``compute_a_conv``  — reference ``ComputeA.linear`` /
  ``ComputeA.conv2d`` (kfac/utils.py:90-128).
* ``compute_g_dense`` / ``compute_g_conv``  — reference ``ComputeG.linear`` /
  ``ComputeG.conv2d`` (kfac/utils.py:131-183).
* ``extract_patches`` — reference ``_extract_patches`` (kfac/utils.py:56-77),
  realised as ``lax.conv_general_dilated_patches`` (XLA's native im2col, which
  tiles onto the MXU) instead of a double ``Tensor.unfold``.
* ``update_running_avg`` — reference kfac/utils.py:80-87. NOTE: the reference
  docstring there is wrong; the *code* computes
  ``current = alpha * current + (1 - alpha) * new`` and that is what we match.

Layout conventions (TPU/flax native, NOT torch):
  * activations NHWC, conv kernels HWIO ``[kh, kw, in, out]``,
    dense kernels ``[in, out]``.
  * the "factor-space" gradient matrix is ``[out, in * kh * kw (+1 bias)]``,
    matching the channel-major patch feature ordering of
    ``conv_general_dilated_patches`` (verified by test_factors.py roundtrips).

All matmuls feeding factors use ``lax.Precision.HIGHEST`` so TPU bf16 matmul
defaults cannot corrupt the eigendecompositions downstream.

Every factor is a Gram matrix ``yᵀ·(y·s)`` and so symmetric. The dense and
conv forms (``compute_a_dense``, ``compute_a_conv``, ``compute_g_dense``,
``compute_g_conv``) are formed in one helper, :func:`_gram`: below
``_GRAM_MIN_SIDE`` columns the single product the reference has, bit for
bit; from there on :func:`gram_blocks` (two Pallas kernels) multiplies only
the column-block pairs on and above the diagonal (``(k+1)/2k`` of the
multiply-adds for ``k`` blocks, same precision, same rows) and mirrors them,
and a bias column is not multiplied at all: its row and column are column
sums. Block width and row tile follow the operand's shape alone; there is no
option. The batched einsum forms (row / column shards, MoE experts, grouped
convs' G side) keep their full products: their sides are small by
construction. Rationale in counts: docs/PERF.md, "Symmetric factor
products"; the chip's readings: root PERF.md, PR 26.

Every ``compute_a_*`` / ``compute_g_*`` runs under the ``kfac_capture`` phase
scope (observability/phases.py): the A products are sown from inside the
model's forward pass, and a device trace tells them from the model's own ops
by that name alone.
"""

from __future__ import annotations

import functools
import operator
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.ops import grouped
from kfac_pytorch_tpu.observability.telemetry import get_telemetry

_HIGHEST = lax.Precision.HIGHEST

# A factor whose operand has at least _GRAM_MIN_SIDE columns is formed from
# blocks of _GRAM_BLOCK columns, over row tiles of the largest of
# _GRAM_ROW_TILES that divides the rows; a narrower one by the single product it
# always was. Chosen on the v5e over 8192 rows (scripts/gram_block_sweep.py;
# root PERF.md, PR 26): blocks of 256 columns over 1024 rows read fastest at the
# sides measured (0.59 of the full product's time at 3072, 0.61 at 2304, 0.75 at
# 768; 384, 512, 768 and 1024 columns and tiles of 512 rows all read slower),
# and a side of 512 read 0.92 of it, which is not worth the second kernel.
_GRAM_MIN_SIDE = 768
_GRAM_BLOCK = 256
_GRAM_ROW_TILES = (1024, 512, 256, 128)

Padding = Union[str, Sequence[Tuple[int, int]]]


def _as_pairs(padding: Padding) -> Padding:
    """Normalize int / int-pair padding into conv_general padding pairs."""
    if isinstance(padding, str):
        return padding
    pairs = []
    for p in padding:
        if isinstance(p, int):
            pairs.append((p, p))
        else:
            pairs.append(tuple(p))
    return tuple(pairs)


def extract_patches(
    x: jnp.ndarray,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    kernel_dilation: Tuple[int, int] = (1, 1),
) -> jnp.ndarray:
    """im2col: ``[B, H, W, C] -> [B, out_h, out_w, C * kh * kw]``.

    Feature dim is channel-major ``(c, kh, kw)``, matching
    ``conv_kernel_to_mat`` column ordering. Parity: kfac/utils.py:56-77.
    """
    return lax.conv_general_dilated_patches(
        x,
        filter_shape=tuple(kernel_size),
        window_strides=tuple(strides),
        padding=_as_pairs(padding),
        rhs_dilation=tuple(kernel_dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _flatten_leading(x: jnp.ndarray) -> jnp.ndarray:
    """``[..., d] -> [N, d]`` — dense layers may see [B, d] or [B, T, d]."""
    return x.reshape(-1, x.shape[-1])


def _gram_tiles(n: int, d: int) -> Optional[Tuple[int, int]]:
    """``(columns per block, rows per tile)`` for an ``[n, d]`` operand, or
    None where the factor stays one product: a narrow side, rows that no tile
    divides, or a program over several devices (a Mosaic call has no
    partitioning rule, so GSPMD would gather the operand onto every device;
    cf. ``flash_attention.best_attention_fn``)."""
    if d < _GRAM_MIN_SIDE or jax.device_count() != 1:
        return None
    if n <= _GRAM_ROW_TILES[0]:
        return _GRAM_BLOCK, n  # one tile: the whole dimension is always a legal block
    for rows in _GRAM_ROW_TILES:
        if n % rows == 0:
            return _GRAM_BLOCK, rows
    return None


# Trace-time counts of the factor products formed since the last
# :func:`reset_capture_tally`: how many took the blocked form, and the
# multiply-adds issued against those of the full ``side x side`` products.
_TALLY = {"blocked": 0, "issued": 0, "full": 0}


def reset_capture_tally() -> None:
    """Start the counts behind ``kfac/capture_gram_blocked`` and
    ``kfac/capture_flops_share`` anew. The step builders call it where the
    capture of one step program starts to trace, so the gauges describe that
    program."""
    _TALLY.update(blocked=0, issued=0, full=0)


Scaling = Tuple[Tuple[str, float], ...]  # (("div", n), ("mul", s), ...), applied in order


def _scaled(y: jnp.ndarray, steps: Scaling) -> jnp.ndarray:
    for op, s in steps:
        y = {"mul": operator.mul, "div": operator.truediv}[op](y, s)
    return y


@functools.partial(
    jax.jit, static_argnames=("scale", "block", "rows", "pre", "border", "interpret")
)
def gram_blocks(
    x: jnp.ndarray,
    scale: Scaling,
    block: int,
    rows: int,
    pre: Scaling = (),
    border: int = 0,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``yᵀ · scaled(y, scale)``, ``y = scaled(x, pre)``, from the column-block
    pairs on and above the diagonal (the scalings are applied to the blocks as
    they are read, so ``y`` is never written out).

    Two Pallas kernels. The first visits the ``k(k+1)/2`` pairs ``i <= j`` of
    the ``k = ⌈d/block⌉`` column blocks and, for each, sums
    ``y[r, i]ᵀ · scale(y[r, j])`` over the row tiles ``r`` into block
    ``(i, j)`` of the result, at ``HIGHEST`` in float32 like the full product;
    a diagonal block is mirrored about its own diagonal when its last tile is
    in. The second writes the transpose of every block above the diagonal
    into its place below it, in the same buffer. The result is exactly
    symmetric. ``rows`` divides ``x.shape[0]``; the last column block may
    overhang (what it reads past the edge lands past the edge and is dropped).
    ``border`` leaves that many rows and columns past ``d`` in the result for
    the caller to fill (the bias row and column; unwritten, or holding what
    overhung). ``interpret`` defaults to the Pallas interpreter off the TPU.
    Jitted with the scalings static: a step program lowers each distinct
    kernel once, not once per layer (lowering 192 Pallas calls cost GPT-2's
    cell 16 s of set-up).
    """
    n, d = x.shape
    side = d + border
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = -(-d // block)
    pairs = [(i, j) for i in range(k) for j in range(i, k)]
    above = [(i, j) for i, j in pairs if i < j]

    def table(ps, axis):
        return jnp.asarray([p[axis] for p in ps], jnp.int32)

    def products(ii, jj, a_ref, c_ref, o_ref):
        p, r = pl.program_id(0), pl.program_id(1)

        @pl.when(r == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += lax.dot_general(
            _scaled(a_ref[...], pre),
            _scaled(c_ref[...], pre + scale),
            (((0,), (0,)), ((), ())),
            precision=_HIGHEST,
            preferred_element_type=o_ref.dtype,
        )

        @pl.when((r == pl.num_programs(1) - 1) & (ii[p] == jj[p]))
        def _():
            o = o_ref[...]
            row = lax.broadcasted_iota(jnp.int32, o.shape, 0)
            col = lax.broadcasted_iota(jnp.int32, o.shape, 1)
            o_ref[...] = jnp.where(row <= col, o, o.T)

    upper = pl.pallas_call(
        products,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(len(pairs), n // rows),
            in_specs=[
                pl.BlockSpec((rows, block), lambda p, r, ii, jj: (r, ii[p])),
                pl.BlockSpec((rows, block), lambda p, r, ii, jj: (r, jj[p])),
            ],
            out_specs=pl.BlockSpec((block, block), lambda p, r, ii, jj: (ii[p], jj[p])),
        ),
        out_shape=jax.ShapeDtypeStruct((side, side), x.dtype),
        interpret=interpret,
    )(table(pairs, 0), table(pairs, 1), x, x)
    if not above:
        return upper

    def mirror(ii, jj, u_ref, o_ref):
        o_ref[...] = u_ref[...].T

    return pl.pallas_call(
        mirror,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(len(above),),
            in_specs=[pl.BlockSpec((block, block), lambda p, ii, jj: (ii[p], jj[p]))],
            out_specs=pl.BlockSpec((block, block), lambda p, ii, jj: (jj[p], ii[p])),
        ),
        out_shape=jax.ShapeDtypeStruct((side, side), x.dtype),
        input_output_aliases={2: 0},  # the blocks it does not visit stay
        interpret=interpret,
    )(table(above, 0), table(above, 1), upper)


def _gram(
    x: jnp.ndarray,
    scale: Scaling,
    *,
    pre: Scaling = (),
    corner: Optional[float] = None,
    tiles: Optional[Tuple[int, int]] = None,
) -> jnp.ndarray:
    """``yᵀ · scaled(y, scale)`` for ``y = scaled([x, 1], pre)``: the one place
    a factor is formed.

    ``x`` is ``[N, d]``; ``scale`` and ``pre`` multiply or divide by scalars.
    ``corner`` says that the factor has a bias column (a column of ones
    appended to ``x`` before ``pre``) and gives the closed form of its
    bias-bias entry ``N · pre(1) · scale(pre(1))``.

    Where :func:`_gram_tiles` gives no tiling this is the single product at
    ``HIGHEST`` it always was, bit for bit. Where it does, the result, a Gram
    matrix and so symmetric, is formed by :func:`gram_blocks` from the
    column-block pairs on and above the diagonal alone: ``(k+1)/2k`` of the
    multiply-adds for ``k`` blocks, at the same precision over the same rows.
    The bias column is then not multiplied: its row and column are the column
    sums of ``y``. ``tiles`` overrides :func:`_gram_tiles` (the sweep that
    chose the constants, scripts/gram_block_sweep.py; ``()`` = one product).
    """
    n, d = x.shape
    side = d + (corner is not None)
    if tiles is None:
        tiles = _gram_tiles(n, d)
    _TALLY["full"] += n * side * side
    if not tiles:
        if corner is not None:
            x = jnp.concatenate([x, jnp.ones((n, 1), dtype=x.dtype)], axis=1)
        y = _scaled(x, pre)
        out = jnp.matmul(y.T, _scaled(y, scale), precision=_HIGHEST)
        _TALLY["issued"] += n * side * side
    else:
        block = tiles[0]
        k = -(-d // block)
        # a factor is a statistic, never differentiated: the A side is traced
        # inside the differentiated forward pass, and the kernels have no JVP
        x = lax.stop_gradient(x)
        out = gram_blocks(x, scale, *tiles, pre=pre, border=side - d)
        if corner is not None:
            one = jnp.ones((), dtype=x.dtype)
            sums = jnp.sum(_scaled(x, pre), axis=0) * _scaled(one, pre + scale)
            out = out.at[:d, d].set(sums).at[d, :d].set(sums).at[d, d].set(corner)
        _TALLY["issued"] += n * block * block * k * (k + 1) // 2
        _TALLY["blocked"] += 1
    tel = get_telemetry()
    tel.set_gauge("kfac/capture_gram_blocked", _TALLY["blocked"])
    tel.set_gauge("kfac/capture_flops_share", _TALLY["issued"] / _TALLY["full"])
    return out


@phase("kfac_capture")
def compute_a_dense(a: jnp.ndarray, has_bias: bool) -> jnp.ndarray:
    """Input covariance for a dense layer: ``A = aᵀ (a / N)``.

    With bias, activations gain a homogeneous-coordinate column of ones so the
    bias is folded into the same Kronecker factor. Parity: kfac/utils.py:119-128.
    """
    a = _flatten_leading(a)
    n = a.shape[0]
    return _gram(a, (("div", n),), corner=1.0 if has_bias else None)


@phase("kfac_capture")
def compute_a_conv(
    a: jnp.ndarray,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    has_bias: bool,
    kernel_dilation: Tuple[int, int] = (1, 1),
) -> jnp.ndarray:
    """Input covariance for a conv layer from NHWC activations.

    Patch-extract, append bias column, scale by 1/spatial_size, then
    ``A = aᵀ (a / B)`` with B the *batch* size (sum runs over B·oh·ow rows).
    Parity: kfac/utils.py:107-117 — including the bias column being appended
    *before* the 1/spatial division (so its entries are 1/spatial_size).
    """
    batch_size = a.shape[0]
    patches = extract_patches(a, kernel_size, strides, padding, kernel_dilation)
    spatial_size = patches.shape[1] * patches.shape[2]
    p = patches.reshape(-1, patches.shape[-1])
    return _gram(
        p,
        (("div", batch_size),),
        pre=(("div", spatial_size),),
        corner=1.0 / spatial_size if has_bias else None,
    )


@phase("kfac_capture")
def compute_a_conv_grouped(
    a: jnp.ndarray,
    groups: int,
    kernel_size: Tuple[int, int],
    strides: Tuple[int, int],
    padding: Padding,
    has_bias: bool,
    kernel_dilation: Tuple[int, int] = (1, 1),
) -> jnp.ndarray:
    """Stacked per-group input covariances for a grouped conv: ``[G, a, a]``.

    A conv with ``feature_group_count=G`` is exactly G independent convs,
    each reading its own ``cin/G`` input-channel slice — so its K-FAC
    approximation is G independent Kronecker pairs, one per group.
    BEYOND-reference capability: the reference's factor math is
    shape-inconsistent for ``groups > 1`` (its ``ComputeA`` builds an
    ``in·kh·kw`` factor against an ``in/groups·kh·kw``-column weight,
    kfac/utils.py:107-117), so it cannot precondition ResNeXt's grouped
    convs at all. The stacked layout batches the per-group ``[a, a]``
    factors for the MXU; downstream they are just G same-shape layers
    (capture.py expands them into ``name#gK`` pseudo-layers).
    """
    b, h, w, c = a.shape
    cg = c // groups
    xg = jnp.moveaxis(a.reshape(b, h, w, groups, cg), 3, 0)  # [G, B, H, W, cg]
    return jax.vmap(
        lambda x: compute_a_conv(
            x, kernel_size, strides, padding, has_bias, kernel_dilation
        )
    )(xg)


@phase("kfac_capture")
def compute_a_row_sharded(a: jnp.ndarray, shards: int) -> jnp.ndarray:
    """Per-shard input covariances for a ROW-sharded dense kernel: ``[T, a/T, a/T]``.

    A row-sharded matmul ``y = Σ_s x_s W_s`` reads T disjoint feature slices
    of its input; the shard lens models each slice as an independent
    Kronecker pair, so the A side is the stack of per-slice covariances
    (*KFAC for Modern Neural Network Architectures*, arxiv 2311.00636).
    No bias column: the bias of a row-sharded layer is not attributable to
    one input shard (layers force ``use_bias=False``). Scaling matches
    :func:`compute_a_dense` (``/N`` rows).
    """
    a = _flatten_leading(a)
    n = a.shape[0]
    am = a.reshape(n, shards, a.shape[-1] // shards)
    return jnp.einsum("nti,ntj->tij", am, am / n, precision=_HIGHEST)


@phase("kfac_capture")
def compute_a_moe(
    x: jnp.ndarray, expert_ids: jnp.ndarray, num_experts: int
) -> jnp.ndarray:
    """Per-expert UNNORMALIZED input-covariance sums: ``[E, a, a]``.

    Expert ``e``'s slot holds ``S_e = (1/N)·Σ_{t: id_t=e} x_t x_tᵀ`` — the
    covariance sum weighted by the GLOBAL 1/N (not per-expert token counts),
    so the leaves stay linear in per-token contributions and a cross-replica
    ``pmean`` of (S_e, f_e) pairs is exact; the token-count normalization
    ``S_e / f_e`` happens at EMA time (preconditioner), after the reduction.

    The [tokens, experts] dispatch one-hot never densifies: each expert's
    rows are selected with a [N] boolean mask (same elementwise product the
    dense one-hot oracle applies column-wise, so the two are bitwise equal).
    """
    x = _flatten_leading(x)
    ids = expert_ids.reshape(-1)
    n = x.shape[0]

    def _one(e):
        xm = x * (ids == e)[:, None].astype(x.dtype)
        return jnp.matmul(xm.T, xm / n, precision=_HIGHEST)

    return jnp.stack([_one(e) for e in range(num_experts)])


@phase("kfac_capture")
def compute_a_bank(
    rows: jnp.ndarray, group_sizes: jnp.ndarray, n_tokens: int
) -> jnp.ndarray:
    """Input covariances of an expert bank over routed rows:
    ``A_e = (1/T) sum_{t in e} x_t x_t^T``, ``[E, a, a]``: those of E dense
    layers over all T rows whose unrouted rows are zero."""
    return grouped.grouped_gram(rows, group_sizes) / n_tokens


@phase("kfac_capture")
def compute_g_bank(
    g: jnp.ndarray, group_sizes: jnp.ndarray, n_tokens, batch_averaged: bool
) -> jnp.ndarray:
    """Grad-output covariances of an expert bank, ``[E, m, m]``, from the
    cotangent of its routed rows' outputs: :func:`compute_g_dense`'s scaling
    with ``N = n_tokens``, the rows the loss is a mean over."""
    n = jnp.asarray(n_tokens, jnp.float32)
    gram = grouped.grouped_gram(g, group_sizes)
    return gram * n if batch_averaged else gram / n


@phase("kfac_capture")
def compute_a_moe_onehot(
    x: jnp.ndarray, expert_ids: jnp.ndarray, num_experts: int
) -> jnp.ndarray:
    """Dense scatter-add oracle for :func:`compute_a_moe` (parity baseline).

    Materializes the [N, E] dispatch one-hot and masks with its columns —
    exactly the program the sparse path must never emit, kept as the
    reference semantics for the bitwise MoE capture test.
    """
    x = _flatten_leading(x)
    n = x.shape[0]
    onehot = jax.nn.one_hot(
        expert_ids.reshape(-1), num_experts, dtype=x.dtype
    )
    out = []
    for e in range(num_experts):
        xm = x * onehot[:, e][:, None]
        out.append(jnp.matmul(xm.T, xm / n, precision=_HIGHEST))
    return jnp.stack(out)


@phase("kfac_capture")
def compute_a_embed(ids: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Input-covariance DIAGONAL for an embedding layer: token frequencies.

    An embedding lookup is a dense layer over one-hot rows, and the covariance
    of one-hot rows is exactly diagonal: ``A = E[xxᵀ] = diag(counts / N)``
    (row ``n`` contributes ``e_{id_n} e_{id_n}ᵀ``). Storing the [vocab]
    diagonal instead of the [vocab, vocab] dense factor is what makes K-FAC
    on embeddings tractable (vocab² would be ~10⁹ entries at 32k tokens) —
    and it is EXACT, not an approximation. Beyond-reference capability: the
    reference preconditions only Linear/Conv2d (kfac_preconditioner.py:103).
    """
    n = ids.size
    counts = jnp.zeros((vocab,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    return counts / n


@phase("kfac_capture")
def compute_a_embed_onehot(ids: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Dense one-hot oracle for :func:`compute_a_embed` (parity/memory baseline).

    Materializes the [N, vocab] one-hot matrix and the full [vocab, vocab]
    dense A factor, then reads its diagonal — exactly the program the
    fast paths must never emit. Kept as the reference semantics for the
    fused token-gather kernel (ops/factor_kernels.py) and as the memory
    baseline for the compile-only embedding-capture regression test: the
    fused path's temporary bytes must stay far below this one's.
    """
    flat = ids.reshape(-1)
    n = flat.shape[0]
    onehot = jax.nn.one_hot(flat, vocab, dtype=jnp.float32)
    dense_a = jnp.matmul(onehot.T, onehot / n, precision=_HIGHEST)
    return jnp.diagonal(dense_a)


@phase("kfac_capture")
def compute_g_dense(g: jnp.ndarray, batch_averaged: bool) -> jnp.ndarray:
    """Grad-output covariance for a dense layer.

    ``G = gᵀ (g · N)`` when the loss was batch-averaged (undoes the 1/N the
    mean loss baked into each row, then averages the N outer products), else
    ``G = gᵀ (g / N)``. Parity: kfac/utils.py:172-183.
    """
    g = _flatten_leading(g)
    n = g.shape[0]
    return _gram(g, (("mul" if batch_averaged else "div", n),))


@phase("kfac_capture")
def compute_g_diag(g: jnp.ndarray, batch_averaged: bool) -> jnp.ndarray:
    """DIAGONAL of the grad-output covariance: ``diag(GᵀG·s)`` without GᵀG.

    The decoder site of a tied embedding/output head contributes grad-output
    statistics over the [vocab] logit axis; the full [vocab, vocab] matrix is
    as intractable as the dense embedding A factor, but the tied table's A
    side is already stored as a diagonal, so only the diagonal of the decoder
    contribution is needed. Scaling matches :func:`compute_g_dense` (×N when
    batch-averaged, /N otherwise).
    """
    g = _flatten_leading(g)
    n = g.shape[0]
    scale = float(n) if batch_averaged else 1.0 / n
    return jnp.sum(g * g, axis=0) * scale


@phase("kfac_capture")
def compute_g_dense_sharded(
    g: jnp.ndarray, shards: int, batch_averaged: bool
) -> jnp.ndarray:
    """Stacked per-shard grad-output covariances for a COLUMN-sharded dense
    kernel: ``[T, m/T, m/T]``.

    A column-sharded matmul's shards produce disjoint output slices, so the
    shard lens's G factor is exactly block-diagonal — each block the
    covariance of one output slice (arxiv 2311.00636). One batched einsum
    (cf. :func:`compute_g_conv_grouped`); scaling matches
    :func:`compute_g_dense` (``×N`` batch-averaged, ``/N`` otherwise).
    """
    g = _flatten_leading(g)
    n = g.shape[0]
    gm = g.reshape(n, shards, g.shape[-1] // shards)
    scale = float(n) if batch_averaged else 1.0 / n
    return jnp.einsum("nti,ntj->tij", gm, gm * scale, precision=_HIGHEST)


@phase("kfac_capture")
def compute_g_moe(g: jnp.ndarray, batch_averaged: bool) -> jnp.ndarray:
    """Per-expert UNNORMALIZED grad-output covariance sums: ``[E, m, m]``.

    ``g`` is the ``[.., E, m]`` cotangent of the dense per-expert output
    tensor — already expert-masked by top-1 routing (a token's rows are zero
    for every expert it did not visit), so the plain contraction IS the
    per-expert masked sum. Scaled like :func:`compute_g_dense` over the
    GLOBAL token count; the per-expert normalization (``/ f_e``) happens at
    EMA time alongside the A side (see :func:`compute_a_moe`).
    """
    g = g.reshape(-1, g.shape[-2], g.shape[-1])
    n = g.shape[0]
    scale = float(n) if batch_averaged else 1.0 / n
    return jnp.einsum("nei,nej->eij", g, g * scale, precision=_HIGHEST)


@phase("kfac_capture")
def compute_g_conv(g: jnp.ndarray, batch_averaged: bool) -> jnp.ndarray:
    """Grad-output covariance for a conv layer from NHWC output-grads.

    Reshape ``[B, oh, ow, C] -> [B·oh·ow, C]``, rescale (×B if batch-averaged,
    ×spatial always), then ``G = gᵀ (g / (B·oh·ow))``.
    Parity: kfac/utils.py:155-170 (torch transposes NCHW→NHWC first; our
    activations are already NHWC so only the reshape remains).
    """
    batch_size = g.shape[0]
    spatial_size = g.shape[1] * g.shape[2]
    gm = g.reshape(-1, g.shape[-1])
    if batch_averaged:
        gm = gm * batch_size
    gm = gm * spatial_size
    rows = gm.shape[0]
    return _gram(gm, (("div", rows),))


@phase("kfac_capture")
def compute_g_conv_grouped(
    g: jnp.ndarray, groups: int, batch_averaged: bool
) -> jnp.ndarray:
    """Stacked per-group grad-output covariances: ``[G, cout/G, cout/G]``.

    One batched einsum instead of G sliced :func:`compute_g_conv` calls —
    with ResNeXt's 32 groups × 16 layers the per-slice form is 512 separate
    tiny matmuls, which bloats trace/compile time; the batched form is a
    single MXU-friendly contraction per layer. Scaling matches
    :func:`compute_g_conv` exactly (×B if batch-averaged, ×spatial, then
    /rows).
    """
    batch_size = g.shape[0]
    spatial_size = g.shape[1] * g.shape[2]
    gm = g.reshape(-1, groups, g.shape[-1] // groups)
    if batch_averaged:
        gm = gm * batch_size
    gm = gm * spatial_size
    return jnp.einsum(
        "ngi,ngj->gij", gm, gm / gm.shape[0], precision=_HIGHEST
    )


def update_running_avg(
    new: jnp.ndarray, current: jnp.ndarray, alpha: float
) -> jnp.ndarray:
    """EMA with ``alpha`` weight on *history*: ``alpha·current + (1-alpha)·new``.

    Matches the reference CODE (kfac/utils.py:85-87), not its docstring; with
    the default ``factor_decay=0.95`` each update keeps 95% history / 5% new.
    Functional (returns the new value) rather than in-place.
    """
    return alpha * current + (1.0 - alpha) * new


def merge_running_avg_buckets(
    bufs: Sequence[jnp.ndarray], axis_name: str, comm_dtype=None
) -> list:
    """Uniform-weight cross-replica merge of locally-accumulated EMA buckets.

    The deferred-factor-communication merge (DP-KFAC, arxiv 2206.15143),
    exact for lockstep replicas because :func:`update_running_avg` is linear
    in its contributions: after ``m`` local updates from a synced value
    ``F0``, replica ``r`` holds

        F_r = α^m·F0 + (1−α)·Σ_j α^(m−1−j)·c_j^(r)

    so the replica mean ``(1/R)·Σ_r F_r`` carries exactly the weight
    ``(1−α)·α^(m−1−j)`` on step j's *mean* contribution — the same weighted
    combination a per-step reduction of the ``c_j`` would have produced.
    Deferral moves WHEN factor traffic crosses the wire, not what the
    running averages converge to. Operates on the comm plane's flat wire
    buckets (parallel/comm.py); ``comm_dtype`` (e.g. bf16) casts only the
    wire payload, each result is restored to its bucket's dtype. With
    ``comm_dtype=None`` the pmean is bitwise what per-leaf f32 pmeans of the
    same values produce (the reduction is elementwise either way).
    """
    out = []
    for buf in bufs:
        wire = buf if comm_dtype is None else buf.astype(comm_dtype)
        out.append(lax.pmean(wire, axis_name).astype(buf.dtype))
    return out


# ---------------------------------------------------------------------------
# Factor-space <-> parameter-space reshapes
# ---------------------------------------------------------------------------


def conv_kernel_to_mat(kernel: jnp.ndarray) -> jnp.ndarray:
    """HWIO conv kernel ``[kh, kw, in, out] -> [out, in*kh*kw]``.

    Column ordering (in, kh, kw) matches the channel-major patch features of
    ``extract_patches``, so factor A's index space aligns with these columns.
    (The torch analog is weight.view(out, -1), kfac_preconditioner.py:279-281.)
    """
    kh, kw, cin, cout = kernel.shape
    return jnp.transpose(kernel, (3, 2, 0, 1)).reshape(cout, cin * kh * kw)


def mat_to_conv_kernel(mat: jnp.ndarray, kernel_shape) -> jnp.ndarray:
    """Inverse of :func:`conv_kernel_to_mat`."""
    kh, kw, cin, cout = kernel_shape
    return jnp.transpose(mat.reshape(cout, cin, kh, kw), (2, 3, 1, 0))


def dense_kernel_to_mat(kernel: jnp.ndarray) -> jnp.ndarray:
    """Flax dense kernel ``[in, out] -> [out, in]`` (factor-space layout)."""
    return kernel.T


def mat_to_dense_kernel(mat: jnp.ndarray, kernel_shape) -> jnp.ndarray:
    """Inverse of :func:`dense_kernel_to_mat`."""
    del kernel_shape
    return mat.T


def grads_to_mat(layer_grads: Dict[str, Any]) -> jnp.ndarray:
    """Layer grad dict ``{'kernel': ..., 'bias'?: ...}`` → ``[out, in(+1)]``.

    Conv kernels are flattened channel-major; a bias grad becomes the final
    column (homogeneous coordinate). Parity: kfac_preconditioner.py:270-286.
    """
    if "embedding" in layer_grads:
        # [vocab, features] table → [features, vocab] ("out" = features,
        # "in" = the one-hot vocab axis); embeddings have no bias.
        return layer_grads["embedding"].T
    kernel = layer_grads["kernel"]
    if kernel.ndim == 4:
        mat = conv_kernel_to_mat(kernel)
    elif kernel.ndim == 2:
        mat = dense_kernel_to_mat(kernel)
    else:
        raise ValueError(f"unsupported kernel rank: {kernel.shape}")
    if "bias" in layer_grads:
        mat = jnp.concatenate([mat, layer_grads["bias"].reshape(-1, 1)], axis=1)
    return mat


def mat_to_grads(mat: jnp.ndarray, kernel_shape, has_bias: bool) -> Dict[str, Any]:
    """Inverse of :func:`grads_to_mat` (kfac_preconditioner.py:303-308)."""
    if has_bias:
        weight_mat, bias_col = mat[:, :-1], mat[:, -1]
    else:
        weight_mat, bias_col = mat, None
    if len(kernel_shape) == 4:
        kernel = mat_to_conv_kernel(weight_mat, kernel_shape)
    else:
        kernel = mat_to_dense_kernel(weight_mat, kernel_shape)
    out = {"kernel": kernel}
    if bias_col is not None:
        out["bias"] = bias_col
    return out
