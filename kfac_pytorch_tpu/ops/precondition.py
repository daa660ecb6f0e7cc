"""Natural-gradient preconditioning in the Kronecker eigenbasis + KL clipping.

Replaces the reference's ``_get_preconditioned_grad`` (triple matmul in the
eigenbasis, kfac_preconditioner.py:288-309) and ``_update_scale_grad`` (global
KL trust-region rescale, kfac_preconditioner.py:311-334) with pure functions.
The KL-clip global scalar stays inside the compiled program so XLA can
schedule the reduction with everything else (no host sync).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kfac_pytorch_tpu import compat
from kfac_pytorch_tpu.observability.telemetry import get_telemetry
from kfac_pytorch_tpu.ops import grouped

_HIGHEST = lax.Precision.HIGHEST
# Eigenbasis rotations default to HIGH (3-pass bf16 error compensation,
# ~f32-accurate for orthonormal Q): the rotations are the EVERY-STEP hot path
# (4 matmuls x ~54 layers on ResNet-50, ~2.5e11 f32 FLOPs) and HIGHEST's
# 6-pass emulation doubles HIGH's MXU passes (time on the chip: not
# measured). Factor/eigh math stays HIGHEST: those feed
# eigendecompositions, where bf16 error is genuinely destructive, and they
# amortize over fac/kfac_update_freq. Measured equal-convergence evidence:
# logs/cifar10_resnet32_*.jsonl (K-FAC curves with HIGH rotations).
_ROTATION_PRECISION = lax.Precision.HIGH


def precondition_mat(
    grad_mat: jnp.ndarray,
    q_a: jnp.ndarray,
    q_g: jnp.ndarray,
    d_a: jnp.ndarray,
    d_g: jnp.ndarray,
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Apply ``(G ⊗ A + damping·I)⁻¹`` to a ``[out, in]`` gradient matrix.

    Rotate into the Kronecker eigenbasis, divide by the damped eigenvalue
    outer sum, rotate back (kfac_preconditioner.py:298-301):

        v1 = QGᵀ · grad · QA
        v2 = v1 / (dG dAᵀ + damping)
        v  = QG · v2 · QAᵀ
    """
    v1 = jnp.matmul(
        jnp.matmul(q_g.T, grad_mat, precision=precision), q_a, precision=precision
    )
    v2 = v1 / (d_g[:, None] * d_a[None, :] + damping)
    return jnp.matmul(
        jnp.matmul(q_g, v2, precision=precision), q_a.T, precision=precision
    )


def shape_groups(
    shapes: Dict[str, Tuple[int, int]]
) -> Dict[Tuple[int, int], list]:
    """Group layer names by exact ``[out, in]`` shape, insertion-ordered.

    The single source of truth for batching order: both the eigen-time
    stacking (:func:`stack_eigen`) and the per-step batched preconditioning
    derive their row order from this, so they can never disagree.
    """
    groups: Dict[Tuple[int, int], list] = {}
    for name, shape in shapes.items():
        groups.setdefault(tuple(shape), []).append(name)
    return groups


def split_eigen_state(
    eigen: Dict[str, Dict[str, jnp.ndarray]],
) -> Tuple[Dict[str, Dict[str, jnp.ndarray]], Dict[str, Dict[str, jnp.ndarray]]]:
    """Split a full per-layer eigen dict into (singletons, stacked groups).

    Same-shape layers are STACKED for the batched rotations and stored ONLY
    in that form — splitting (rather than duplicating) matters twice over:
    the Q matrices are the dominant HBM stream of the every-step path
    (~480 MB f32 on ResNet-50), so (a) re-stacking per step would double
    that traffic for ~99 of every 100 steps (stacks rebuild only when the
    eigendecompositions change, every ``kfac_update_freq`` steps), and (b)
    carrying both forms would double K-FAC state and checkpoint size.
    Singleton-shape layers stay per-layer (no stack copy needed). Stack keys
    are ``"{out}x{in}"`` (pytree-safe); row order within a stack is the
    insertion order of :func:`shape_groups`, which the per-step grad
    stacking in :func:`precondition_all` re-derives identically.
    """
    return _split_state(eigen, g_key="QG", a_key="QA")


def _split_state(
    state: Dict[str, Dict[str, jnp.ndarray]], g_key: str, a_key: str
) -> Tuple[Dict[str, Dict[str, jnp.ndarray]], Dict[str, Dict[str, jnp.ndarray]]]:
    """Shared singles/stacked split: one implementation of the state-layout
    contract (shape derivation from the ``g_key``/``a_key`` matrices,
    ``"{g}x{a}"`` stack keys, :func:`shape_groups` row order) for both the
    eigen and inverse methods, so the layouts :func:`_stack_layout` assumes
    are identical cannot drift apart. Diagonal-A entries (embeddings — no
    ``a_key`` matrix) always stay singles; :func:`diag_a_names` identifies
    them for the grad-side grouping so both sides exclude the same set."""
    singles: Dict[str, Dict[str, jnp.ndarray]] = {}
    square = {}
    for n, e in state.items():
        if a_key not in e:
            singles[n] = e
        else:
            square[n] = e
    shapes = {
        n: (e[g_key].shape[0], e[a_key].shape[0]) for n, e in square.items()
    }
    stacked: Dict[str, Dict[str, jnp.ndarray]] = {}
    for (g, a), names in shape_groups(shapes).items():
        if len(names) < 2:
            singles[names[0]] = square[names[0]]
            continue
        keys = square[names[0]].keys()
        stacked[f"{g}x{a}"] = {
            k: jnp.stack([square[n][k] for n in names]) for k in keys
        }
    return singles, stacked


def diag_a_names(eigen: Dict[str, Dict[str, jnp.ndarray]]) -> set:
    """Layers whose A factor is a stored diagonal (embeddings): their state
    entry carries eigenvalues/inverses for the A side but no A-side matrix."""
    return {
        n
        for n, e in eigen.items()
        if ("QA" not in e and "iA" not in e) and ("dA" in e or "iA_diag" in e)
    }


def precondition_mat_embed(
    grad_mat: jnp.ndarray,
    q_g: jnp.ndarray,
    d_g: jnp.ndarray,
    d_a: jnp.ndarray,
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Eigenbasis solve for a diagonal-A (embedding) layer.

    A diagonal factor's eigenvectors are the identity, so the A-side
    rotations vanish: ``v = QG · [(QGᵀ·g) / (dG dAᵀ + λ)]`` — exact
    ``(G ⊗ A + λI)⁻¹`` on ``[features, vocab]`` gradients at the cost of two
    G-side matmuls plus elementwise work on the vocab axis."""
    v1 = jnp.matmul(q_g.T, grad_mat, precision=precision)
    v2 = v1 / (d_g[:, None] * d_a[None, :] + damping)
    return jnp.matmul(q_g, v2, precision=precision)


# ---------------------------------------------------------------------------
# Low-rank-plus-diagonal (Woodbury) apply path — solver="rsvd"
#
# A side the randomized solver truncated stores (Q_r [n, r], d_r [r], rho)
# modelling the factor as  F ≈ Q_r diag(d_r) Q_rᵀ + rho·(I − Q_r Q_rᵀ).
# Because Q_r's columns are orthonormal, (G ⊗ A + λI)⁻¹ splits EXACTLY over
# the four sectors (captured/complement on each side): project the gradient
# onto each sector, divide by that sector's damped eigenvalue product
# (complement sides contribute the scalar rho), and re-expand. Every
# operation is a thin [n, r] matmul or elementwise work — per-step cost drops
# from O(n²) to O(n·r) per truncated side, and the eigen state the sharded
# refresh broadcasts shrinks by the same factor. Low-rank entries reuse the
# dense state keys (QA/dA/QG/dG) at rectangular shapes plus a scalar
# ``rhoA``/``rhoG``; key presence is the dispatch signal
# (:func:`solve_eigen_entry`).
# ---------------------------------------------------------------------------


def precondition_mat_lowrank(
    grad_mat: jnp.ndarray,
    q_a: jnp.ndarray,
    q_g: jnp.ndarray,
    d_a: jnp.ndarray,
    d_g: jnp.ndarray,
    rho_a: jnp.ndarray,
    rho_g: jnp.ndarray,
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Woodbury solve with BOTH sides truncated: ``q_a [in, rA]``, ``q_g
    [out, rG]``, eigenvalues ``d_a [rA]``/``d_g [rG]``, residual masses
    ``rho_a``/``rho_g`` (scalars).

    Sector decomposition of ``(G ⊗ A + λI)⁻¹``: captured×captured divides by
    ``d_g d_aᵀ + λ``, captured×complement by ``d_g·rho_a + λ`` (and its
    mirror), complement×complement by ``rho_g·rho_a + λ``. The identity-minus-
    projector complements never materialize: the full-gradient term carries
    the complement×complement inverse and the thin projections subtract the
    double-counted sectors.
    """
    lam = damping
    t1 = jnp.matmul(q_g.T, grad_mat, precision=precision)  # [rG, in]
    t2 = jnp.matmul(grad_mat, q_a, precision=precision)  # [out, rA]
    t3 = jnp.matmul(t1, q_a, precision=precision)  # [rG, rA]
    c4 = 1.0 / (rho_g * rho_a + lam)
    d2 = 1.0 / (d_g * rho_a + lam)  # [rG]
    d3 = 1.0 / (rho_g * d_a + lam)  # [rA]
    z = (
        t3 / (d_g[:, None] * d_a[None, :] + lam)
        - d2[:, None] * t3
        - t3 * d3[None, :]
        + c4 * t3
    )
    x = (d2 - c4)[:, None] * t1 + jnp.matmul(z, q_a.T, precision=precision)
    y = t2 * (d3 - c4)[None, :]
    return (
        c4 * grad_mat
        + jnp.matmul(q_g, x, precision=precision)
        + jnp.matmul(y, q_a.T, precision=precision)
    )


def precondition_mat_lr_g(
    grad_mat: jnp.ndarray,
    q_a: jnp.ndarray,
    q_g: jnp.ndarray,
    d_a: jnp.ndarray,
    d_g: jnp.ndarray,
    rho_g: jnp.ndarray,
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Woodbury solve with only the G side truncated (``q_g [out, rG]``,
    ``rho_g`` scalar); the A side keeps its full eigenbasis ``q_a [in, in]``.
    Rotate fully on the A side, split captured/complement on the G side."""
    lam = damping
    g_a = jnp.matmul(grad_mat, q_a, precision=precision)  # [out, in]
    t1 = jnp.matmul(q_g.T, g_a, precision=precision)  # [rG, in]
    cap = t1 / (d_g[:, None] * d_a[None, :] + lam)
    res = (g_a - jnp.matmul(q_g, t1, precision=precision)) / (
        rho_g * d_a[None, :] + lam
    )
    return jnp.matmul(
        jnp.matmul(q_g, cap, precision=precision) + res,
        q_a.T,
        precision=precision,
    )


def precondition_mat_lr_a(
    grad_mat: jnp.ndarray,
    q_a: jnp.ndarray,
    q_g: jnp.ndarray,
    d_a: jnp.ndarray,
    d_g: jnp.ndarray,
    rho_a: jnp.ndarray,
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Woodbury solve with only the A side truncated (``q_a [in, rA]``,
    ``rho_a`` scalar); the G side keeps its full eigenbasis."""
    lam = damping
    g_g = jnp.matmul(q_g.T, grad_mat, precision=precision)  # [out, in]
    t = jnp.matmul(g_g, q_a, precision=precision)  # [out, rA]
    cap = t / (d_g[:, None] * d_a[None, :] + lam)
    res = (g_g - jnp.matmul(t, q_a.T, precision=precision)) / (
        d_g[:, None] * rho_a + lam
    )
    return jnp.matmul(
        q_g,
        jnp.matmul(cap, q_a.T, precision=precision) + res,
        precision=precision,
    )


def precondition_mat_embed_lr_g(
    grad_mat: jnp.ndarray,
    q_g: jnp.ndarray,
    d_g: jnp.ndarray,
    rho_g: jnp.ndarray,
    d_a: jnp.ndarray,
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Diagonal-A (embedding) layer with a truncated G side: the A rotations
    are still the identity, and the G side splits captured/complement."""
    lam = damping
    t1 = jnp.matmul(q_g.T, grad_mat, precision=precision)  # [rG, vocab]
    cap = jnp.matmul(
        q_g, t1 / (d_g[:, None] * d_a[None, :] + lam), precision=precision
    )
    res = (grad_mat - jnp.matmul(q_g, t1, precision=precision)) / (
        rho_g * d_a[None, :] + lam
    )
    return cap + res


def entry_is_lowrank(e: Dict[str, jnp.ndarray]) -> bool:
    """Whether an eigen-state entry carries a truncated (Woodbury) side."""
    return "rhoA" in e or "rhoG" in e


def solve_eigen_entry(
    g: jnp.ndarray,
    e: Dict[str, jnp.ndarray],
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Dispatch one layer's eigenbasis solve on its state-entry keys.

    Dense entries route to the exact pre-existing functions with identical
    arguments (bit-for-bit inert when no ``rho*`` key is present); low-rank
    entries route to the matching Woodbury form. The single dispatcher is
    shared by the per-layer replicated loop, the vmapped stacked path, and
    the owner-sharded distributed solve.
    """
    if "QA" not in e:  # diagonal-A (embedding) layer
        if "rhoG" in e:
            return precondition_mat_embed_lr_g(
                g, e["QG"], e["dG"], e["rhoG"], e["dA"], damping, precision
            )
        return precondition_mat_embed(
            g, e["QG"], e["dG"], e["dA"], damping, precision
        )
    lr_a, lr_g = "rhoA" in e, "rhoG" in e
    if lr_a and lr_g:
        return precondition_mat_lowrank(
            g, e["QA"], e["QG"], e["dA"], e["dG"], e["rhoA"], e["rhoG"],
            damping, precision,
        )
    if lr_g:
        return precondition_mat_lr_g(
            g, e["QA"], e["QG"], e["dA"], e["dG"], e["rhoG"], damping,
            precision,
        )
    if lr_a:
        return precondition_mat_lr_a(
            g, e["QA"], e["QG"], e["dA"], e["dG"], e["rhoA"], damping,
            precision,
        )
    return precondition_mat(
        g, e["QA"], e["QG"], e["dA"], e["dG"], damping, precision
    )


def precondition_all(
    grad_mats: Dict[str, jnp.ndarray],
    eigen: Dict[str, Dict[str, jnp.ndarray]],
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
    stacked: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None,
) -> Dict[str, jnp.ndarray]:
    """Precondition every layer's gradient matrix, batching same-shape layers.

    The per-layer loop hands XLA ~54 sequential small triple-matmul chains on
    ResNet-50 — each too small to fill the MXU. Layers whose ``[out, in]``
    shapes coincide (bottleneck blocks repeat identical shapes 3-6x) are
    preconditioned with ONE batched einsum chain instead; results come back
    keyed as given. Exact-shape grouping keeps the math bit-identical to
    :func:`precondition_mat` (no padding; matmul has no per-shape compile
    cliff to bucket around, unlike eigh — see ops/eigh.py). ``stacked``
    (from :func:`split_eigen_state`, carried in KFAC state) supplies the
    group eigen tensors pre-stacked; a group absent from ``stacked`` is
    stacked on the fly from per-layer entries (legacy full-format states).
    """
    diag_a = diag_a_names(eigen)
    out: Dict[str, jnp.ndarray] = {}
    # sorted: set iteration order varies per process under hash
    # randomization, and dict insertion order feeds the KL-clip summation
    # order — cross-host bitwise determinism requires a fixed order
    for name in sorted(diag_a):
        out[name] = solve_eigen_entry(
            grad_mats[name], eigen[name], damping, precision
        )
    shapes = {
        name: g.shape for name, g in grad_mats.items() if name not in diag_a
    }
    for (go, ai), names in shape_groups(shapes).items():
        if len(names) == 1:
            name = names[0]
            out[name] = solve_eigen_entry(
                grad_mats[name], eigen[name], damping, precision
            )
            continue
        gm = jnp.stack([grad_mats[n] for n in names])  # [k, out, in]
        key = f"{go}x{ai}"
        if stacked is not None and key in stacked:
            s = stacked[key]
        else:
            keys = eigen[names[0]].keys()
            s = {k: jnp.stack([eigen[n][k] for n in names]) for k in keys}
        if entry_is_lowrank(s):
            # vmap of the single-matrix Woodbury solve = the same batched
            # matmuls the dense einsum chain gets, at the thin [n, r] shapes
            v = jax.vmap(
                lambda g, e: solve_eigen_entry(g, e, damping, precision)
            )(gm, s)
            for row, name in enumerate(names):
                out[name] = v[row]
            continue
        qa, qg, da, dg = s["QA"], s["QG"], s["dA"], s["dG"]
        v1 = jnp.einsum("kji,kjl->kil", qg, gm, precision=precision)
        v1 = jnp.einsum("kil,klm->kim", v1, qa, precision=precision)
        v2 = v1 / (dg[:, :, None] * da[:, None, :] + damping)
        v = jnp.einsum("kij,kjl->kil", qg, v2, precision=precision)
        v = jnp.einsum("kil,kml->kim", v, qa, precision=precision)
        for row, name in enumerate(names):
            out[name] = v[row]
    return out


def _stack_layout(
    shapes: Dict[str, Tuple[int, int]],
    stacked: Optional[Dict[str, Dict[str, jnp.ndarray]]],
    diag_a: set = frozenset(),
) -> Dict[str, Optional[Tuple[str, int]]]:
    """``name -> None (per-layer entry) | (stack_key, row)``.

    Shared by the distributed paths; derives the same grouping and row order
    as :func:`split_eigen_state`/:func:`precondition_all` (shape_groups is
    the single source of truth). ``diag_a`` layers (embeddings) are excluded
    from grouping exactly as :func:`_split_state` excludes them — a
    diagonal-A layer whose grad shape coincides with a dense stack must not
    shift that stack's row indices."""
    where: Dict[str, Optional[Tuple[str, int]]] = {n: None for n in diag_a}
    shapes = {n: s for n, s in shapes.items() if n not in diag_a}
    for (go, ai), names in shape_groups(shapes).items():
        key = f"{go}x{ai}"
        if len(names) == 1 or stacked is None or key not in stacked:
            for n in names:
                where[n] = None
        else:
            for row, n in enumerate(names):
                where[n] = (key, row)
    return where


def _apply_distributed(
    grad_mats: Dict[str, jnp.ndarray],
    singles: Dict[str, Dict[str, jnp.ndarray]],
    stacked: Optional[Dict[str, Dict[str, jnp.ndarray]]],
    damping: jnp.ndarray,
    mesh: Mesh,
    owners: Dict[str, int],
    solve_fn,
    comm_dtype: Optional[Any] = None,
) -> Dict[str, jnp.ndarray]:
    """SPMD skeleton for owner-sharded per-layer preconditioning.

    Each layer's solve runs only on its owner device (FLAT index over all
    mesh axes, like the eigh table) inside one ``shard_map``: non-owners
    contribute zeros and a single ``psum`` of the update pytree reassembles —
    the eigh sharding's sum-of-zeros exchange (parallel/sharded_eigh.py)
    applied to the every-step path. ``lax.cond`` is a real branch on the
    owner predicate — XLA does not flatten conditionals whose branches
    contain dots — so non-owners skip the matmuls AND the curvature-state
    HBM reads at run time. ``solve_fn(g, entry, damping)`` receives the
    layer's state entry (stacked groups row-sliced inside the owner branch
    only, so only owners pay the slice copy).

    ``comm_dtype`` (e.g. ``jnp.bfloat16``) downcasts the exchanged updates
    for the psum and casts back to f32 after — halving the wire bytes, the
    TPU analog of the reference's Horovod fp16 allreduce compression
    (``--fp16-allreduce``, pytorch_cifar10_resnet.py:190-195). Exact when a
    slot has ONE owner (each element is a single device's value plus zeros,
    so the sum itself adds no error beyond the downcast rounding).
    """
    axes = tuple(mesh.axis_names)
    diag_a = diag_a_names(singles)
    where = _stack_layout(
        {n: g.shape for n, g in grad_mats.items()},
        stacked,
        diag_a,
    )
    # Emit updates in precondition_all's order (sorted diag-A first, then
    # shape_groups order): dict insertion order feeds the KL-clip summation,
    # so the distributed and replicated paths must reassociate identically
    # for their results to match bitwise, not just to tolerance.
    order = sorted(diag_a) + [
        n
        for names in shape_groups(
            {n: g.shape for n, g in grad_mats.items() if n not in diag_a}
        ).values()
        for n in names
    ]

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    def _inner(gmats, sing, stacks, damp):
        dev = lax.axis_index(axes[0])
        for a in axes[1:]:
            dev = dev * mesh.shape[a] + lax.axis_index(a)
        out: Dict[str, jnp.ndarray] = {}
        for name in order:
            g = gmats[name]
            loc = where[name]

            def _solve(name=name, g=g, loc=loc):
                if loc is None:
                    entry = sing[name]
                else:
                    key, row = loc
                    entry = {k: v[row] for k, v in stacks[key].items()}
                return solve_fn(g, entry, damp)

            dtype = comm_dtype or jnp.float32
            out[name] = lax.cond(
                dev == owners[name],
                lambda _s=_solve, dtype=dtype: _s().astype(dtype),
                lambda g=g, dtype=dtype: jnp.zeros(g.shape, dtype),
            )
        # Sum-of-zeros exchange: one allreduce over the whole update pytree.
        out = lax.psum(out, axes)
        if comm_dtype is not None:
            out = {n: v.astype(jnp.float32) for n, v in out.items()}
        return out

    return _inner(grad_mats, singles, stacked or {}, damping)


def precondition_all_distributed(
    grad_mats: Dict[str, jnp.ndarray],
    eigen: Dict[str, Dict[str, jnp.ndarray]],
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
    stacked: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None,
    *,
    mesh: Mesh,
    owners: Dict[str, int],
    comm_dtype: Optional[Any] = None,
) -> Dict[str, jnp.ndarray]:
    """Eigenbasis preconditioning with rotations SHARDED across the mesh.

    The replicated path (:func:`precondition_all`) has every device rotate
    every layer's gradient — the reference's behavior (each Horovod rank
    redundantly preconditions all layers, kfac_preconditioner.py:401-404) and
    a fixed ~2.2e11-FLOP/step tax on ResNet-50 regardless of device count.
    Owner-sharding (``owners`` from parallel.assignment.
    precondition_assignment) shrinks per-device rotation FLOPs and
    eigenvector HBM traffic ~1/world; the added comm is one allreduce of the
    preconditioned K-FAC grads (~the size of the grad allreduce the step
    already does), riding ICI with the step's other collectives. Results
    match :func:`precondition_all` (see _apply_distributed).
    """

    def _solve(g, e, damp):
        return solve_eigen_entry(g, e, damp, precision)

    return _apply_distributed(
        grad_mats, eigen, stacked, damping, mesh, owners, _solve, comm_dtype
    )


def _owner_gather_layout(
    shapes: Dict[str, Tuple[int, int]],
    owners: Dict[str, int],
    world: int,
    rank_fn,
    diag_a: set = frozenset(),
) -> Tuple[list, Dict[str, Dict[str, Any]], int]:
    """Static allgather-buffer layout for the owner-sharded solve.

    Per layer, pick the cheaper wire payload (DP-KFAC §IV): the
    preconditioned ``[g, a]`` update, or — when the randomized solver
    truncates a side and the compact Q/d/ρ tables are smaller — the tables
    themselves, re-solved replicated after the gather. Returns
    ``(order, segments, per_device_elems)`` where ``order`` is
    :func:`precondition_all`'s canonical emission order (KL-clip summation
    order), ``segments[name]`` carries the mode, the owner-buffer offset and
    the table field layout, and ``per_device_elems`` is the uniform f32
    buffer width (max owned payload over devices).
    """
    order = sorted(diag_a) + [
        n
        for names in shape_groups(
            {k: v for k, v in shapes.items() if k not in diag_a}
        ).values()
        for n in names
    ]
    segments: Dict[str, Dict[str, Any]] = {}
    cursor = [0] * world
    for name in order:
        g, a = int(shapes[name][0]), int(shapes[name][1])
        diag = name in diag_a
        ra = rank_fn(a) if rank_fn is not None and not diag else None
        rg = rank_fn(g) if rank_fn is not None else None
        if diag:
            # diagonal-A layer: the A side is already a compact [vocab]
            # vector; only the G side can carry a truncated basis
            fields = [("dA", (a,))]
        else:
            fields = [
                ("QA", (a, ra) if ra is not None else (a, a)),
                ("dA", (ra,) if ra is not None else (a,)),
            ]
            if ra is not None:
                fields.append(("rhoA", ()))
        fields += [
            ("QG", (g, rg) if rg is not None else (g, g)),
            ("dG", (rg,) if rg is not None else (g,)),
        ]
        if rg is not None:
            fields.append(("rhoG", ()))
        def _elems(shape: Tuple[int, ...]) -> int:
            size = 1
            for d in shape:
                size *= int(d)
            return size

        table_elems = sum(_elems(s) for _, s in fields)
        update_elems = g * a
        mode = (
            "tables"
            if (diag or ra is not None or rg is not None)
            and table_elems < update_elems
            else "update"
        )
        elems = table_elems if mode == "tables" else update_elems
        owner = owners[name]
        segments[name] = {
            "mode": mode,
            "offset": cursor[owner],
            "elems": elems,
            "fields": tuple(fields),
        }
        cursor[owner] += elems
    return order, segments, max(1, max(cursor))


def precondition_all_owner(
    grad_mats: Dict[str, jnp.ndarray],
    eigen_shard: Dict[str, Dict[str, jnp.ndarray]],
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
    *,
    mesh: Mesh,
    plan,
    rank_fn=None,
    eigen_dtype=jnp.float32,
    axis_name: str = None,
) -> Dict[str, jnp.ndarray]:
    """Owner-sharded preconditioning: solve on the owner, allgather results.

    The ``factor_sharding="owner"`` hot path (DP-KFAC, arxiv 2206.15143):
    each layer's eigenbasis lives ONLY in its owner's shard rows, so the
    owner runs :func:`solve_eigen_entry` against its local shard (a
    ``lax.cond`` on the flat device index — non-owners skip the matmuls and
    the shard HBM reads), packs the flat result into its slice of a uniform
    per-device buffer, and ONE ``lax.all_gather`` replicates every layer's
    payload (pinned by ``scripts/check_collective_count.py``). Layers whose
    compact rsvd tables beat the dense update on the wire ship Q/d/ρ instead
    and re-solve replicated after the gather (:func:`_owner_gather_layout`).
    Updates come back in :func:`precondition_all`'s emission order so the
    KL-clip summation reassociates identically.
    """
    from kfac_pytorch_tpu.observability.telemetry import get_telemetry

    axes = tuple(mesh.axis_names)
    if axis_name is None:
        if len(axes) != 1:
            raise ValueError(
                "owner-sharded preconditioning on a multi-axis mesh needs "
                f"an explicit axis_name; got axes {axes}"
            )
        axis = axes[0]
    else:
        # a tuple means the joint batch axes of a 3-D data×fsdp×tensor
        # mesh: the owner index space is their row-major flattening
        # (axis_index/all_gather/PartitionSpec all agree on that order)
        names = (
            (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        )
        missing = [a for a in names if a not in axes]
        if missing:
            raise ValueError(
                f"axis {axis_name!r} not in mesh axes {axes}"
            )
        axis = names[0] if isinstance(axis_name, str) else tuple(names)
    axis_world = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        axis_world *= int(mesh.shape[a])
    if axis_world != plan.world:
        raise ValueError(
            f"shard plan world {plan.world} != mesh axis {axis!r} size "
            f"{axis_world}"
        )
    shapes = {n: (g.shape[0], g.shape[1]) for n, g in grad_mats.items()}
    diag_a = {
        s.name for s in plan.slots if s.factor == "A" and s.diag
    }
    order, segments, width = _owner_gather_layout(
        shapes, plan.owners, plan.world, rank_fn, diag_a
    )
    get_telemetry().set_gauge(
        "kfac/precond_allgather_bytes", plan.world * width * 4
    )

    def _entry(eshard, name):
        g_n, a_n = shapes[name]
        out = {}
        for fac, n in (("A", a_n), ("G", g_n)):
            slot = plan.slot(name, fac)
            if slot.diag:
                # vector group: the eigen entry is the floored diagonal
                out[f"d{fac}"] = eshard[f"v{n}"]["d"][slot.row]
                continue
            grp = eshard[f"n{n}"]
            out[f"Q{fac}"] = grp["Q"][slot.row]
            out[f"d{fac}"] = grp["d"][slot.row]
            if "rho" in grp:
                out[f"rho{fac}"] = grp["rho"][slot.row]
        return out

    eigen_specs = jax.tree_util.tree_map(lambda _: P(axis), eigen_shard)

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(P(), eigen_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    def _inner(gmats, eshard, damp):
        dev = lax.axis_index(axis)
        buf = jnp.zeros((width,), jnp.float32)
        for name in order:
            seg = segments[name]

            def _payload(name=name, seg=seg):
                entry = _entry(eshard, name)
                if seg["mode"] == "update":
                    v = solve_eigen_entry(
                        gmats[name], entry, damp, precision
                    )
                    return v.astype(jnp.float32).reshape(-1)
                parts = [
                    entry[k].astype(jnp.float32).reshape(-1)
                    for k, _ in seg["fields"]
                ]
                return jnp.concatenate(parts)

            off, elems = seg["offset"], seg["elems"]
            buf = lax.cond(
                dev == plan.owners[name],
                lambda b, _p=_payload, off=off, elems=elems: b.at[
                    off : off + elems
                ].set(_p()),
                lambda b: b,
                buf,
            )
        # the single preconditioned-gradient allgather of the owner mode
        return lax.all_gather(buf, axis)  # [world, width], replicated

    gathered = _inner(grad_mats, eigen_shard, damping)

    out: Dict[str, jnp.ndarray] = {}
    for name in order:
        seg = segments[name]
        g_n, a_n = shapes[name]
        payload = gathered[plan.owners[name], seg["offset"] : seg["offset"] + seg["elems"]]
        if seg["mode"] == "update":
            out[name] = payload.reshape(g_n, a_n)
            continue
        entry = {}
        off = 0
        for k, shp in seg["fields"]:
            size = 1
            for d in shp:
                size *= int(d)
            val = payload[off : off + size].reshape(shp)
            off += size
            if k.startswith("Q"):
                # round-trip through the storage dtype so the replicated
                # re-solve sees the exact bits the owner's shard holds
                val = val.astype(eigen_dtype)
            entry[k] = val
        out[name] = solve_eigen_entry(grad_mats[name], entry, damping, precision)
    return out


# ---------------------------------------------------------------------------
# Inverse-method preconditioning (precond_method="inverse")
#
# The reference preconditions in the Kronecker EIGENbasis with the damping
# applied to the eigenvalue outer sum (kfac_preconditioner.py:298-301) — the
# exact (G ⊗ A + λI)⁻¹ solve, at 4 matmuls per layer EVERY step. The classic
# alternative (Martens & Grosse'15 §6.3 factored Tikhonov damping; also the
# default in the reference's successor library) folds the damping INTO the
# factors and preconditions with explicit inverses:
#
#     π  = sqrt( (tr(A)/dim A) / (tr(G)/dim G) )
#     iA = (A + π·√λ·I)⁻¹ ,  iG = (G + (√λ/π)·I)⁻¹
#     v  = iG · grad · iA                       (2 matmuls per step)
#
# Per-step FLOPs and curvature-state HBM traffic HALVE vs the eigenbasis
# path (docs/PERF.md), and the amortized inverse computation is one sweep of
# matrix products (n³ multiply-adds, below) instead of an eigendecomposition
# (~10n³). The tradeoffs:
# (G ⊗ A + λ·I)⁻¹ is approximated by the factored damping, and a damping
# schedule only takes effect at the next curvature refresh (the eigen path
# applies λ fresh every step). Opt-in via KFAC(precond_method="inverse").
# ---------------------------------------------------------------------------


# Pivot width of the refresh's sweep. A pivot step reads and writes the whole
# stack (8·n² bytes a matrix) and multiplies n²·b at float32, six bf16 passes:
# on a v5e (197 TFLOP/s, 819 GB/s) it is bound by memory below b ≈ 160, so
# 256 is the least multiple of the 128-wide matrix unit that is bound by
# arithmetic. A side of 256 or less is one pivot: a Cholesky of the whole. A
# remainder is a narrower last pivot: joined to the pivot before it, it took
# the temporaries of a [12, 3073, 3073] sweep compiled for a v5e from 0.95 GB
# to 4.85 GB.
SWEEP_BLOCK = 256

# Trace-time counts behind the gauges of this module since the last
# :func:`reset_tallies` (the precedent is ops/factors.py::_TALLY): banks
# preconditioned from their routed rows, and serial pivot steps of the refresh.
_TALLY = {"routed": 0, "pivots": 0}


def reset_tallies() -> None:
    """Start the counts behind ``kfac/apply_bank_routed`` and
    ``kfac/refresh_sweep_pivots`` anew, at 0. The step builders call it where
    a step program starts to trace, so the gauges describe that program (0 in
    one that preconditions no bank by rows, or refreshes nothing)."""
    _TALLY.update(routed=0, pivots=0)
    get_telemetry().set_gauge("kfac/apply_bank_routed", 0)
    get_telemetry().set_gauge("kfac/refresh_sweep_pivots", 0)


def _count_pivots(side: int, sweeps: int = 1) -> None:
    """Add ``sweeps`` inversions of side ``side`` to the refresh's pivot count."""
    _TALLY["pivots"] += sweeps * -(-side // SWEEP_BLOCK)
    get_telemetry().set_gauge("kfac/refresh_sweep_pivots", _TALLY["pivots"])


def _cholesky_inverse(stack: jnp.ndarray) -> jnp.ndarray:
    """Batched SPD inverse by a Cholesky and two triangular solves against the
    identity: ``[k, n, n] -> [k, n, n]``, not symmetrised."""
    k, n, _ = stack.shape
    eye = jnp.broadcast_to(jnp.eye(n, dtype=stack.dtype), (k, n, n))
    chol = lax.linalg.cholesky(stack)
    y = lax.linalg.triangular_solve(chol, eye, left_side=True, lower=True)
    return lax.linalg.triangular_solve(
        chol, y, left_side=True, lower=True, transpose_a=True
    )


def _sweep_pivot(m: jnp.ndarray, lo, b: int) -> jnp.ndarray:
    """One Gauss-Jordan pivot step over rows and columns ``lo .. lo + b`` of
    the stack ``m`` ``[k, n, n]`` whose rows and columns before ``lo`` are
    swept, as one rank-b update of the whole. With ``P = M[k,k]⁻¹`` the step
    sets ``M[k,:] ← P·M[k,:]``, ``M[:,k] ← −M[:,k]·P``, ``M[k,k] ← P`` and
    ``M[i,j] ← M[i,j] − M[i,k]·P·M[k,j]`` elsewhere, which is
    ``M − (M[:,k] − E)·P·(M[k,:] + Eᵀ)`` with ``E`` the identity's columns
    ``lo .. lo + b``.

    The pivot is a Schur complement of an SPD matrix, so SPD itself:
    ``M[k,k] = L·Lᵀ`` by a Cholesky of side b, and ``P = L⁻ᵀ·L⁻¹`` is split
    between the two factors, as a Cholesky forms the Schur complement (with
    ``P`` whole the complement loses a factor of the pivot's condition number:
    NaN at 1e6 in float32). The sweep keeps the rows of ``M[:,k]`` the
    negated (swept) or plain (not yet swept) transposes of ``M[k,:]``'s
    columns, so the left factor is the right one transposed, its swept rows
    negated and its pivot block ``(M[k,k] − I)·L⁻ᵀ = L − L⁻ᵀ``: the column
    panel is never read."""
    n = m.shape[-1]
    eye = jnp.eye(b, dtype=m.dtype)
    rows = lax.dynamic_slice_in_dim(m, lo, b, axis=1)
    pivot = lax.dynamic_slice_in_dim(rows, lo, b, axis=2)
    # its lower triangle alone: symmetrising it reads the stack in a second
    # layout, and the compiler then copies the whole stack twice a pivot
    chol = lax.linalg.cholesky(pivot, symmetrize_input=False)
    chol_inv = lax.linalg.triangular_solve(
        chol, jnp.broadcast_to(eye, pivot.shape), left_side=True, lower=True
    )
    right = chol_inv @ lax.dynamic_update_slice_in_dim(rows, pivot + eye, lo, axis=2)
    sign = jnp.where(jnp.arange(n) < lo, -1.0, 1.0).astype(m.dtype)
    left = jnp.swapaxes(right, -1, -2) * sign[:, None]
    left = lax.dynamic_update_slice_in_dim(
        left, chol - jnp.swapaxes(chol_inv, -1, -2), lo, axis=1
    )
    return m - left @ right


def _spd_inverse_stack(stack: jnp.ndarray) -> jnp.ndarray:
    """Batched SPD inverse: ``[k, n, n] -> [k, n, n]``, by an in-place sweep
    of ``SWEEP_BLOCK``-wide pivots (:func:`_sweep_pivot`; a narrower last one
    where the width does not divide the side), n³ multiply-adds a matrix in
    batched products; one Cholesky of the whole where ``n <= SWEEP_BLOCK``.

    Every product runs at float32 (six bf16 passes): bf16 dots corrupt the
    inverse the same way they corrupt eigenvectors (ops/eigh.py).
    """
    n = stack.shape[-1]
    full, rest = divmod(n, SWEEP_BLOCK)
    with jax.default_matmul_precision("float32"):
        if n <= SWEEP_BLOCK:
            inv = _cholesky_inverse(stack)
        else:
            inv = lax.fori_loop(
                0, full,
                lambda j, m: _sweep_pivot(m, j * SWEEP_BLOCK, SWEEP_BLOCK),
                stack,
            )
            if rest:
                inv = _sweep_pivot(inv, full * SWEEP_BLOCK, rest)
    return 0.5 * (inv + jnp.swapaxes(inv, -1, -2))


def factored_inverse_all(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    damping: jnp.ndarray,
    eps: float = 1e-10,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """``{layer: {'A', 'G'}} -> {layer: {'iA', 'iG'}}`` with π-corrected
    factored Tikhonov damping (see module comment above). Same-side factors
    batch into one sweep each (exact-shape grouping, like
    :func:`precondition_all`'s matmul batching)."""
    names = list(factors)
    sqrt_l = jnp.sqrt(damping.astype(jnp.float32))
    pis = {}
    for n in names:
        f = factors[n]
        # trace(A)/dim: for a stored-diagonal A (embedding) that's just the
        # mean of the diagonal vector
        if "A_diag" in f:
            tr_a = jnp.maximum(jnp.mean(f["A_diag"]), eps)
        else:
            tr_a = jnp.maximum(jnp.trace(f["A"]) / f["A"].shape[0], eps)
        g_f = f["G"]
        tr_g = jnp.maximum(jnp.trace(g_f) / g_f.shape[0], eps)
        pis[n] = jnp.sqrt(tr_a / tr_g)

    jobs: Dict[int, list] = {}
    out: Dict[str, Dict[str, jnp.ndarray]] = {n: {} for n in names}
    for n in names:
        if "A_diag" in factors[n]:
            # diagonal A inverts elementwise; only G needs the sweep
            out[n]["iA_diag"] = 1.0 / (
                factors[n]["A_diag"].astype(jnp.float32) + pis[n] * sqrt_l
            )
        else:
            jobs.setdefault(factors[n]["A"].shape[0], []).append((n, "A"))
        jobs.setdefault(factors[n]["G"].shape[0], []).append((n, "G"))
    for side, batch in sorted(jobs.items()):
        stack = jnp.stack(
            [factors[n][f].astype(jnp.float32) for n, f in batch]
        )
        damps = jnp.stack(
            [pis[n] * sqrt_l if f == "A" else sqrt_l / pis[n] for n, f in batch]
        )
        eye = jnp.eye(side, dtype=jnp.float32)
        inv = _spd_inverse_stack(stack + damps[:, None, None] * eye)
        _count_pivots(side)
        for row, (n, f) in enumerate(batch):
            out[n]["iA" if f == "A" else "iG"] = inv[row]
    return out


# ---------------------------------------------------------------------------
# Inverse tables: the inverse method's state for models with expert banks.
# Every inverse of one side lives in ONE array ``[K, side, side]`` (a bank's E
# inverses in a row), so that a refresh writes the damped factors into the
# table and inverts it where it lies, a batch at a time: the refresh holds no
# second copy of some hundred factors of side 2048. ``layout`` (static, from
# shapes alone) says where each layer's ``iA`` and ``iG`` lie.
# ---------------------------------------------------------------------------


# Bytes of float32 matrices that one batch of a table's refresh holds: an
# eighth of a v5e's 16 GiB. The sweep works in place, so a batch costs about
# its own bytes in temporaries where it is the whole table and twice its bytes
# beside the rest of the table (described-v5e compiles, PERF.md): a batch
# holds at most a quarter of the chip in temporaries, where a Cholesky batch
# cost some eight times its bytes.
TABLE_BATCH_BYTES = 2 * 2**30


def _batches(count: int, side: int) -> Tuple[int, int]:
    """``(batches, matrices per batch)`` for ``count`` float32 matrices of
    ``side`` in batches of at most ``TABLE_BATCH_BYTES``, as equal as may be."""
    per = max(1, min(count, TABLE_BATCH_BYTES // (4 * side * side)))
    batches = -(-count // per)
    return batches, -(-count // batches)


def _pis(factors, shared_a, eps):
    """π = sqrt((tr A / dim A) / (tr G / dim G)) per layer (per expert for a
    bank's stacks), a ``shared_a`` layer reading its owner's ``A``."""
    mean_diag = lambda m: jnp.trace(m, axis1=-2, axis2=-1) / m.shape[-1]
    pis = {}
    for n, f in factors.items():
        tr_a = jnp.maximum(mean_diag(factors[shared_a.get(n, n)]["A"]), eps)
        tr_g = jnp.maximum(mean_diag(f["G"]), eps)
        pis[n] = jnp.sqrt(tr_a / tr_g)
    return pis


def inverse_table_layout(
    shapes: Dict[str, Dict[str, Tuple[int, ...]]],
    shared_a: Dict[str, str],
) -> Tuple[Dict[str, Dict[str, Tuple[int, int, int]]], Dict[int, int]]:
    """``({layer: {'iA'|'iG': (side, first row, rows)}}, {side: rows of its
    table})`` from ``{layer: {'A'?, 'G': shape}}``; ``rows`` is 1 for a plain
    layer and E for a bank. A table's row count is a whole number of refresh
    batches (its last rows may be padding, kept at the identity)."""
    layout: Dict[str, Dict[str, Tuple[int, int, int]]] = {}
    used: Dict[int, int] = {}
    for name, f in shapes.items():
        layout[name] = {}
        for key, shape in (("iA", shapes[shared_a.get(name, name)]["A"]), ("iG", f["G"])):
            side, rows = shape[-1], (shape[0] if len(shape) == 3 else 1)
            layout[name][key] = (side, used.get(side, 0), rows)
            used[side] = used.get(side, 0) + rows
    table_rows = {}
    for side, k in used.items():
        batches, per = _batches(k, side)
        table_rows[side] = batches * per
    return layout, table_rows


def factored_inverse_tables(
    factors: Dict[str, Dict[str, jnp.ndarray]],
    tables: Dict[str, jnp.ndarray],
    damping: jnp.ndarray,
    eps: float,
    shared_a: Dict[str, str],
    layout: Dict[str, Dict[str, Tuple[int, int, int]]],
) -> Dict[str, jnp.ndarray]:
    """The refresh of :func:`factored_inverse_all` into the inverse tables
    ``{str(side): [K, side, side]}``, each written where it lies: a loop over
    the table's batches (one batch's sweep), each step damping its
    batch's factors (``lax.switch`` over the batches: which factors those
    are is static) and writing their inverses over the table's old rows, so
    that no second copy of a side's factors exists. A bank is damped (its own
    π per expert) and inverted per expert; a layer of ``shared_a`` reads its
    owner's ``A`` and still gets an ``iA`` of its own, because π is the
    layer's own. Padding rows stay at the identity."""
    sqrt_l = jnp.sqrt(damping.astype(jnp.float32))
    pis = _pis(factors, shared_a, eps)
    rows_of: Dict[int, list] = {}  # side -> [(factor, shift, row of a bank or None)], in table order
    for name in layout:
        for key in ("iA", "iG"):
            side, first, rows = layout[name][key]
            if key == "iA":
                m, shift = factors[shared_a.get(name, name)]["A"], pis[name] * sqrt_l
            else:
                m, shift = factors[name]["G"], sqrt_l / pis[name]
            assert first == len(rows_of.setdefault(side, []))
            rows_of[side] += [(m, shift, e if m.ndim == 3 else None) for e in range(rows)]
    out = {}
    for side, rows in rows_of.items():
        table = tables[str(side)]
        eye = jnp.eye(side, dtype=jnp.float32)
        batches, per = _batches(table.shape[0], side)

        def damped(lo, rows=rows, eye=eye, per=per):
            """Rows ``lo .. lo + per`` of the table, damped, before inversion."""
            parts, i = [], lo
            while i < min(lo + per, len(rows)):
                m, shift, e = rows[i]
                if e is None:
                    parts.append((m.astype(jnp.float32) + shift * eye)[None])
                    i += 1
                    continue
                n = min(m.shape[0] - e, lo + per - i)  # a run of one bank's experts
                parts.append(m[e:e + n].astype(jnp.float32) + shift[e:e + n, None, None] * eye)
                i += n
            pad = max(0, lo + per - len(rows))  # only the last batch has any
            if pad:
                parts.append(jnp.broadcast_to(eye, (pad, side, side)))
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        _count_pivots(side, batches)
        branches = [partial(damped, j * per) for j in range(batches)]

        def body(j, table, branches=branches, per=per):
            inverse = _spd_inverse_stack(lax.switch(j, branches))
            return lax.dynamic_update_slice_in_dim(table, inverse, j * per, 0)

        out[str(side)] = lax.fori_loop(0, batches, body, table)
    return out


def precondition_all_inv_tables(
    grad_mats: Dict[str, jnp.ndarray],
    tables: Dict[str, jnp.ndarray],
    layout: Dict[str, Dict[str, Tuple[int, int, int]]],
    precision: lax.Precision = _ROTATION_PRECISION,
) -> Dict[str, jnp.ndarray]:
    """``v = iG · grad · iA`` per layer, the inverses read from their tables;
    a bank's ``[E, m, a]`` gradient against its E rows."""
    out = {}
    for name, g in grad_mats.items():
        inv = {}
        for key, (side, first, rows) in layout[name].items():
            rows_of = tables[str(side)][first:first + rows]
            inv[key] = rows_of if g.ndim == 3 else rows_of[0]
        out[name] = precondition_mat_inv(g, inv["iA"], inv["iG"], precision)
    return out


def bank_rows_pay(rows: int, experts: int, a: int, m: int) -> bool:
    """Whether the update of an ``[E, a, m]`` bank from ``rows`` routed rows
    (:func:`precondition_bank_rows`: ``2·M·(a² + m² + a·m)`` multiply-adds)
    takes no more multiply-adds than the dense ``iG · g · iA``
    (``2·E·(m²·a + m·a²)``) even when every row is real. Static: from shapes
    alone. It counts multiply-adds, not passes: on the Mosaic kernels (one
    device) the routed products run at float32, about twice the passes of the
    dense form's ``high`` (ops/grouped.py), so there the routed form costs no
    more only while about half the bound's rows or fewer are real. For
    GLM-4.7-Flash's gate and up banks (8,192 rows against a bound of 9,299)
    that is about 4,650 rows in the held groups, 4.5 times the mean load of
    about 1,024; with every row in a held group the routed form takes about
    1.76 times the dense form's passes."""
    return rows * (a * a + m * m + a * m) <= experts * (m * m * a + m * a * a)


def precondition_bank_rows(
    rows: jnp.ndarray,
    cotangents: jnp.ndarray,
    group_sizes: jnp.ndarray,
    tables: Dict[str, jnp.ndarray],
    layout: Dict[str, Tuple[int, int, int]],
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """A bank's ``v`` from the rows each expert saw, in the kernel's
    ``[E, a, m]`` layout (the transpose of :func:`precondition_all_inv_tables`'
    ``[E, m, a]``): the bank's gradient is ``g_e = ΔY_eᵀ X_e`` over its routed
    rows, so ``v_eᵀ = iA_e · g_eᵀ · iG_e = (X_e · iA_e)ᵀ (ΔY_e · iG_e)`` (the
    inverses are symmetric), three grouped products over the ``M`` rows
    (``rows`` ``[M, a]``, ``cotangents`` ``[M, m]``, sorted by expert) in
    place of two dense ones over every expert's ``[m, a]``. The same
    arithmetic, not an approximation; the inverses are read where they lie in
    their tables (``layout``: the layer's entry of
    :func:`inverse_table_layout`)."""
    (side_a, first_a, _), (side_g, first_g, _) = layout["iA"], layout["iG"]
    x_ia = grouped.grouped_project(rows, tables[str(side_a)], first_a, group_sizes, precision)
    dy_ig = grouped.grouped_project(cotangents, tables[str(side_g)], first_g, group_sizes, precision)
    _TALLY["routed"] += 1
    get_telemetry().set_gauge("kfac/apply_bank_routed", _TALLY["routed"])
    return grouped.grouped_outer(x_ia, dy_ig, group_sizes, precision)


def split_inv_state(
    inv: Dict[str, Dict[str, jnp.ndarray]],
) -> Tuple[Dict[str, Dict[str, jnp.ndarray]], Dict[str, Dict[str, jnp.ndarray]]]:
    """Inverse-method analog of :func:`split_eigen_state`: same-shape layers
    live only as stacked ``{'iA': [k,a,a], 'iG': [k,g,g]}`` groups."""
    return _split_state(inv, g_key="iG", a_key="iA")


def precondition_mat_inv(
    grad_mat: jnp.ndarray,
    i_a: jnp.ndarray,
    i_g: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """``v = iG · grad · iA`` — the 2-matmul inverse-method solve."""
    return jnp.matmul(
        jnp.matmul(i_g, grad_mat, precision=precision), i_a, precision=precision
    )


def precondition_mat_inv_embed(
    grad_mat: jnp.ndarray,
    i_a_diag: jnp.ndarray,
    i_g: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
) -> jnp.ndarray:
    """Inverse-method solve for a diagonal-A (embedding) layer:
    ``v = (iG · grad) ⊙ iA_diag``."""
    return jnp.matmul(i_g, grad_mat, precision=precision) * i_a_diag[None, :]


def precondition_all_inv(
    grad_mats: Dict[str, jnp.ndarray],
    inv: Dict[str, Dict[str, jnp.ndarray]],
    precision: lax.Precision = _ROTATION_PRECISION,
    stacked: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None,
) -> Dict[str, jnp.ndarray]:
    """Inverse-method twin of :func:`precondition_all` (same-shape batching,
    same stack layout contract)."""
    diag_a = diag_a_names(inv)
    out: Dict[str, jnp.ndarray] = {}
    # sorted: set iteration order varies per process under hash
    # randomization, and dict insertion order feeds the KL-clip summation
    # order — cross-host bitwise determinism requires a fixed order
    for name in sorted(diag_a):
        e = inv[name]
        out[name] = precondition_mat_inv_embed(
            grad_mats[name], e["iA_diag"], e["iG"], precision
        )
    shapes = {
        name: g.shape for name, g in grad_mats.items() if name not in diag_a
    }
    for (go, ai), names in shape_groups(shapes).items():
        if len(names) == 1:
            name = names[0]
            e = inv[name]
            out[name] = precondition_mat_inv(
                grad_mats[name], e["iA"], e["iG"], precision
            )
            continue
        gm = jnp.stack([grad_mats[n] for n in names])
        key = f"{go}x{ai}"
        if stacked is not None and key in stacked:
            ia, ig = stacked[key]["iA"], stacked[key]["iG"]
        else:
            ia = jnp.stack([inv[n]["iA"] for n in names])
            ig = jnp.stack([inv[n]["iG"] for n in names])
        v = jnp.einsum("kij,kjl->kil", ig, gm, precision=precision)
        v = jnp.einsum("kil,klm->kim", v, ia, precision=precision)
        for row, name in enumerate(names):
            out[name] = v[row]
    return out


def precondition_all_inv_distributed(
    grad_mats: Dict[str, jnp.ndarray],
    inv: Dict[str, Dict[str, jnp.ndarray]],
    damping: jnp.ndarray,
    precision: lax.Precision = _ROTATION_PRECISION,
    stacked: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None,
    *,
    mesh: Mesh,
    owners: Dict[str, int],
    comm_dtype: Optional[Any] = None,
) -> Dict[str, jnp.ndarray]:
    """Owner-sharded inverse-method solve (see :func:`_apply_distributed`).
    ``damping`` is unused at solve time (it was folded into the inverses) but
    kept in the signature so both methods share the distributed skeleton."""

    def _solve(g, e, _damp):
        if "iA_diag" in e:  # diagonal-A (embedding) layer
            return precondition_mat_inv_embed(g, e["iA_diag"], e["iG"], precision)
        return precondition_mat_inv(g, e["iA"], e["iG"], precision)

    return _apply_distributed(
        grad_mats, inv, stacked, damping, mesh, owners, _solve, comm_dtype
    )


def kl_clip_coefficient(
    updates: Dict[str, jnp.ndarray],
    grad_mats: Dict[str, jnp.ndarray],
    lr: jnp.ndarray,
    kl_clip: float,
) -> jnp.ndarray:
    """Global trust-region scale ν = min(1, sqrt(kl_clip / |Σ v·g·lr²|)).

    The sum runs over every preconditioned layer (kfac_preconditioner.py:
    320-326); callers multiply every update by the returned scalar. A tiny
    floor guards the 0/0 case (all-zero grads) that the reference's
    ``abs(vg_sum)`` would turn into a ZeroDivisionError.
    """
    vg_sum = jnp.asarray(0.0, dtype=jnp.float32)
    for name, v in updates.items():
        g = grad_mats[name]
        vg_sum = vg_sum + jnp.sum(v.astype(jnp.float32) * g.astype(jnp.float32)) * (
            lr**2
        )
    denom = jnp.maximum(jnp.abs(vg_sum), 1e-30)
    return jnp.minimum(1.0, jnp.sqrt(kl_clip / denom))
