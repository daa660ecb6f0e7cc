"""Weights from the seed, made by the benchmark on the device in one jitted
call, in float32 (the type the configurations train in). The program's own
initializers are not used: the reference has to start from the same weights
and may take nothing that the program has made, so both are handed these.

The seed is a traced argument, so every seed runs the same compiled program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def leaf_paths(tree):
    """``[(name, leaf)]`` with ``a/b/c`` names, in flattening order."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append(("/".join(str(getattr(p, "key", p)) for p in path), leaf))
    return out


def _std(rule, fan_in):
    if rule == "he_fan_in":
        return math.sqrt(2.0 / fan_in)
    if rule == "lecun_fan_in":
        return math.sqrt(1.0 / fan_in)
    return float(rule)


def make_weights(shapes, seed, rules):
    """A tree like ``shapes`` (of ShapeDtypeStructs) filled from ``seed``
    (a uint32 scalar, possibly traced) by the configuration's ``weights``
    rules: kernels and embeddings normal with the stated spread, scales and
    biases constant."""
    base = jax.random.fold_in(jax.random.PRNGKey(0), seed)
    leaves = []
    for i, (name, s) in enumerate(leaf_paths(shapes)):
        kind = name.rsplit("/", 1)[-1]
        key = jax.random.fold_in(base, i)
        if kind == "kernel" and len(s.shape) == 4:
            kh, kw, cin, _ = s.shape
            w = _std(rules["conv_kernel"], kh * kw * cin) * jax.random.normal(key, s.shape)
        elif kind == "kernel":  # [fan-in, fan-out], or a bank of them, [experts, fan-in, fan-out]
            w = _std(rules["dense_kernel"], s.shape[-2]) * jax.random.normal(key, s.shape)
        elif kind == "embedding":
            w = _std(rules["embedding"], s.shape[1]) * jax.random.normal(key, s.shape)
        elif kind in ("scale", "bias"):
            w = jnp.full(s.shape, rules[kind])
        else:
            raise ValueError(f"no weight rule for parameter {name!r}")
        leaves.append(w.astype(jnp.float32))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes), leaves)


def seed_scalar(seed: int):
    """``--seed`` (any whole number a little over 2**31) as a uint32."""
    return jnp.uint32(int(seed) % (1 << 32))
