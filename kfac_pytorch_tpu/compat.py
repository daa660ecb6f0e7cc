"""The two JAX entry points whose spelling has moved between releases, kept
under one name each so call sites do not churn. Written for the one
installation there is (jax 0.9.0): no branch for any other.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``; keyword-only after ``f``, so
    ``partial(shard_map, mesh=..., in_specs=..., out_specs=...,
    check_vma=False)`` decorator usage works."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def tpu_compiler_params(**kwargs):
    """``pallas.tpu.CompilerParams``."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
