"""Grouped products over rows sorted by group: an expert bank's projections
and its per-expert Gram matrices.

``rows`` is ``[M, a]`` with the groups' rows in order and the groups first;
``group_sizes`` ``[E]`` says how many rows each group has. Rows past their sum
belong to no group: ``grouped_matmul`` reads and writes them as zeros, forward
and backward, whatever the kernel underneath leaves there (a grouped kernel
does not touch them, so they hold what the memory held); ``grouped_project``
leaves them as the kernel does, and ``grouped_outer`` reads only the groups'
own rows.

On one device the products are the Pallas grouped kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` and ``tgmm``, the
Pallas interpreter off the TPU), whose grid follows the row tiles the groups
really fill and whose calls carry the caller's name scopes, so that a device
trace can charge them to a phase. XLA's own ``ragged_dot`` does the same work
on the TPU through a kernel it names itself (``ragged-dot-none``), which no
phase can claim (PERF.md, PR 28); it stays the path of programs over several
devices, where a Mosaic call has no partitioning rule
(cf. ``ops/factors.py::_gram_tiles``).

The Mosaic products know two precisions, one bfloat16 pass and float32 (a
``dot`` at ``high``, three passes, is refused by the kernel's compiler): a
product asked for above one pass runs at float32 there, and at what was asked
for on the ``ragged_dot`` path.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
from jax import lax

# (rows, contracted, columns) per tile of a projection, and of a Gram (whose
# rows are the contracted dimension): chosen on the v5e at 8192 rows in 8
# groups of about 128 to 1024 (scripts/grouped_sweep.py; PERF.md, PR 28)
_MATMUL_TILES = (128, 512, 512)
_GRAM_TILES = (128, 512, 512)


def _kernels():
    ops = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.ops")
    backend = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
    return ops.gmm, backend.tgmm


def _use_kernels(rows: int) -> bool:
    return jax.device_count() == 1 and rows % _MATMUL_TILES[0] == 0


def _in_groups(rows: int, group_sizes: jnp.ndarray) -> jnp.ndarray:
    return (jnp.arange(rows) < jnp.sum(group_sizes))[:, None]


def grouped_matmul(rows: jnp.ndarray, kernel: jnp.ndarray, group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``[M, m]``: row ``t`` of group ``e`` times ``kernel[e]`` (``[E, a, m]``),
    at the default matmul precision; differentiable in ``rows`` and
    ``kernel``."""
    valid = _in_groups(rows.shape[0], group_sizes)
    rows = jnp.where(valid, rows, 0)  # and so is the gradient that comes back for them
    if _use_kernels(rows.shape[0]):
        gmm, _ = _kernels()
        out = gmm(rows, kernel, group_sizes, jnp.float32, _MATMUL_TILES, None, None, False,
                  jax.default_backend() != "tpu")
    else:
        out = lax.ragged_dot(rows, kernel, group_sizes)
    return jnp.where(valid, out, 0)


def _kernel_precision(precision) -> str:
    return "default" if precision in (None, lax.Precision.DEFAULT) else "highest"


def grouped_outer(
    lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, precision,
) -> jnp.ndarray:
    """``[E, a, m]``: ``lhs_e^T rhs_e`` over each group's own rows of ``lhs``
    ``[M, a]`` and ``rhs`` ``[M, m]``, in float32; rows past the groups count
    for nothing, whatever they hold (both kernels select each group's own
    rows). A group of no rows gives zeros. Not differentiated."""
    if _use_kernels(lhs.shape[0]):
        _, tgmm = _kernels()
        with jax.default_matmul_precision(_kernel_precision(precision)):
            return tgmm(lhs.T, rhs, group_sizes, jnp.float32, _GRAM_TILES,
                        interpret=jax.default_backend() != "tpu")
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[],
    )
    return lax.ragged_dot_general(
        lhs, rhs, group_sizes, dims, precision=precision,
        preferred_element_type=jnp.float32,
    )


def grouped_gram(x: jnp.ndarray, group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``[E, d, d]``: ``x_e^T x_e`` over each group's own rows of ``x``
    ``[M, d]``, in float32 at ``highest`` as every factor product. Not
    differentiated (a statistic)."""
    x = lax.stop_gradient(x)
    return grouped_outer(x, x, group_sizes, lax.Precision.HIGHEST)


def grouped_project(
    rows: jnp.ndarray, table: jnp.ndarray, first: int, group_sizes: jnp.ndarray,
    precision,
) -> jnp.ndarray:
    """``[M, n]``: row ``t`` of group ``e`` times ``table[first + e]`` (``table``
    ``[K, k, n]``, the E matrices read where they lie: the groups before
    ``first`` and after ``first + E`` are empty, so no slice of the table is
    copied out for the kernel), in float32: rows of a narrower dtype are
    widened here, where they are read. Rows past the groups hold what the
    kernel leaves there. Not differentiated."""
    rows = rows.astype(jnp.float32)
    count = group_sizes.shape[0]
    sizes = jnp.zeros((table.shape[0],), jnp.int32).at[first:first + count].set(group_sizes)
    if _use_kernels(rows.shape[0]):
        gmm, _ = _kernels()
        with jax.default_matmul_precision(_kernel_precision(precision)):
            return gmm(rows, table, sizes, jnp.float32, _MATMUL_TILES, None, None, False,
                       jax.default_backend() != "tpu")
    return lax.ragged_dot(rows, table, sizes, precision=precision,
                          preferred_element_type=jnp.float32)
