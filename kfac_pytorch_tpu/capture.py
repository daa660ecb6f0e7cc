"""Extract per-layer K-FAC statistics from flax variable/grad pytrees.

The functional replacement for the reference's hook-state dictionaries
(``m_a``/``m_g`` keyed by module object, kfac_preconditioner.py:109-114):
layers are keyed by their '/'-joined module path, and all artifacts for one
layer — kernel/bias grads in ``params``, the A-factor contribution in
``kfac_acts``, the output-gradient in the ``perturbations`` cotangent — share
that key by construction (see models/layers.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from kfac_pytorch_tpu.models.layers import (
    A_BANK,
    A_COL,
    A_CONTRIB,
    A_MOE,
    A_ROW,
    A_SHARED,
    A_SPLIT,
    BANK_INPUT,
    BANK_ROWS,
    BANK_TOKENS,
    G_TIED,
    N_MOE,
    OUT_MOE,
    OUT_PERTURB,
    OUT_TIED,
)
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.ops import factor_kernels, factors

PyTree = Any

# Grouped-conv pseudo-layer naming: a KFACConv with feature_group_count=G
# sows a stacked [G, a, a] A contribution and is expanded into G entries
# "path#g0".."path#g{G-1}" — each an ordinary same-shape layer to everything
# downstream (factor EMA, bucketed eigh, stacked rotations, round-robin
# assignment). "#" cannot appear in flax module paths, so the suffix is
# unambiguous.
GROUP_SEP = "#g"

# Expand-lens pseudo-layer naming: a KFACDense with lens_splits=S (fused
# QKV) sows a stacked [S, a, a] A contribution under ``a_lens`` and expands
# into "path#s0".."path#s{S-1}". Unlike grouped convs (which partition BOTH
# factor sides), a lens split shares the full A side and partitions only the
# output/G side into features/S columns.
SPLIT_SEP = "#s"

# Shard-lens naming (kfac_pytorch_tpu/shardwise/): unlike "#gK"/"#sK" (one
# pseudo-layer per index), ONE name carries the whole shard stack — the
# per-shard factors stay stacked in state so the tensor-axis layout
# (shardwise.lenses) can place each block on the device that owns the
# matching kernel shard.
#   "path#c{T}"  column-sharded dense (T kernel column shards): replicated A,
#                block-diagonal per-shard G stack [T, m/T, m/T].
#   "path#r{T}"  row-sharded dense (T kernel row shards): per-shard A slices
#                [T, a/T, a/T], one shared G (the psum'd output grad).
#   "path#e{E}"  MoE expert bank (E experts): per-expert A/G stacks with
#                token-count-weighted EMAs.
COL_SEP = "#c"
ROW_SEP = "#r"
MOE_SEP = "#e"
_SHARD_SEPS = {"c": COL_SEP, "r": ROW_SEP, "e": MOE_SEP}


# Expert-bank naming (models/layers.py::KFACBankDense): "path#b{E}" is ONE
# layer whose kernel is [E, a, m] and whose factors and inverses stay stacked
# [E, ., .]. Not a shard-lens name: a bank runs through the generic flow
# (plain decay, the inverse method), with a leading expert dimension.
BANK_SEP = "#b"


def split_bank_name(name: str) -> Tuple[str, Any]:
    """``"path#b8" -> ("path", 8)``; any other name ``-> (name, None)``."""
    base, sep, count = name.rpartition(BANK_SEP)
    if sep and count.isdigit():
        return base, int(count)
    return name, None


def split_shard_name(name: str) -> Tuple[str, Any, Any]:
    """``"path#c4" -> ("path", "c", 4)``; unsharded ``-> (name, None, None)``.

    The form tag is ``"c"`` (column-sharded), ``"r"`` (row-sharded) or
    ``"e"`` (MoE expert bank); the count is the shard/expert count the layer
    sowed (NOT a pseudo-layer index — shard stacks are never expanded into
    per-index entries).
    """
    for form, sep in _SHARD_SEPS.items():
        base, s, count = name.rpartition(sep)
        if s and count.isdigit():
            return base, form, int(count)
    return name, None, None


def is_shard_name(name: str) -> bool:
    """Whether ``name`` carries a shard-lens suffix (``#c``/``#r``/``#e``)."""
    return split_shard_name(name)[1] is not None


def split_group_name(name: str) -> Tuple[str, Any]:
    """``"path#g3" -> ("path", 3)``; ungrouped ``"path" -> ("path", None)``."""
    base, sep, idx = name.rpartition(GROUP_SEP)
    if not sep:
        return name, None
    return base, int(idx)


def split_lens_name(name: str) -> Tuple[str, Any]:
    """``"path#s2" -> ("path", 2)``; unsplit ``"path" -> ("path", None)``."""
    base, sep, idx = name.rpartition(SPLIT_SEP)
    if not sep:
        return name, None
    return base, int(idx)


def layer_base(name: str) -> str:
    """Module path with any pseudo-layer/shard suffix stripped
    (``#gK``/``#sK``/``#cT``/``#rT``/``#eE``)."""
    base, gi = split_group_name(name)
    if gi is not None:
        return base
    base, form, _ = split_shard_name(name)
    if form is not None:
        return base
    base, count = split_bank_name(name)
    if count is not None:
        return base
    return split_lens_name(name)[0]


def group_counts(names: List[str]) -> Dict[str, int]:
    """``{base_path: G}`` for every grouped base present in ``names``."""
    counts: Dict[str, int] = {}
    for n in names:
        base, gi = split_group_name(n)
        if gi is not None:
            counts[base] = max(counts.get(base, 0), gi + 1)
    return counts


def lens_counts(names: List[str]) -> Dict[str, int]:
    """``{base_path: S}`` for every lens-split base present in ``names``."""
    counts: Dict[str, int] = {}
    for n in names:
        base, si = split_lens_name(n)
        if si is not None:
            counts[base] = max(counts.get(base, 0), si + 1)
    return counts


def _flatten_with_paths(tree: PyTree) -> List[Tuple[Tuple[str, ...], Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        keys = tuple(
            p.key if isinstance(p, jax.tree_util.DictKey) else str(p) for p in path
        )
        out.append((keys, leaf))
    return out


def layer_names(params: PyTree) -> List[str]:
    """Heuristic K-FAC layer list: module paths with rank-2/4 ``kernel`` leaves.

    Mirrors the reference's ``known_modules = {'Linear', 'Conv2d'}`` scan
    (kfac_preconditioner.py:103). Correct when every rank-2/4 ``kernel`` in
    the model belongs to a capture-aware KFACDense/KFACConv; models mixing in
    other kernel-bearing modules (e.g. grouped convs, plain nn.Dense) must
    use :func:`discover_layers` and pass the result to ``KFAC(layers=...)``.
    DELIBERATELY excludes ``embedding`` params: a plain ``nn.Embed`` is
    common and non-capturing, so KFACEmbed layers are picked up only by
    :func:`discover_layers` (which sees the sown contribution) or an
    explicit ``layers=`` list — every example trainer uses the former.
    Order is the sorted flattened-path order — deterministic across
    processes, as the layer→device assignment requires.
    """
    names = []
    for keys, leaf in _flatten_with_paths(params):
        if keys[-1] == "kernel" and leaf.ndim in (2, 4):
            names.append("/".join(keys[:-1]))
    return names


def layer_names_from_capture(captured: PyTree) -> List[str]:
    """Authoritative layer list: paths that sowed an A contribution.

    A rank-3 contribution ``[G, a, a]`` marks a grouped conv, expanded into
    G ``path#gK`` pseudo-layers (rank 2 = dense/conv, rank 1 = embedding
    diagonal). An ``a_lens`` contribution ``[S, a, a]`` marks an expand-lens
    dense layer (fused QKV), expanded into S ``path#sK`` pseudo-layers.
    A shard-lens contribution (``a_col``/``a_row``/``a_moe``) marks a
    sharded-parameter layer and yields ONE ``path#cT``/``path#rT``/``path#eE``
    name carrying the stack size in the suffix (shard stacks stay stacked).
    """
    shard_keys = {A_COL: COL_SEP, A_ROW: ROW_SEP, A_MOE: MOE_SEP}
    # a bank says so by its row counts (its A stack may be a sibling's);
    # a_shared marks a plain layer whose A statistic a sibling owns
    a_keys = (A_CONTRIB, A_SPLIT, A_SHARED, BANK_ROWS) + tuple(shard_keys)
    bank_paths = {
        keys[: -1 if keys[-1] == BANK_ROWS else -2]
        for keys, _ in _flatten_with_paths(captured)
        if BANK_ROWS in keys[-2:]
    }
    names = []
    for keys, leaf in _flatten_with_paths(captured):
        # sow may wrap the leaf in a tuple (path gains an index key)
        key = keys[-1] if keys[-1] in a_keys else (
            keys[-2] if len(keys) >= 2 and keys[-2] in a_keys
            else None
        )
        if key is None:
            continue
        path = keys[: -1 if keys[-1] == key else -2]
        name = "/".join(path)
        if path in bank_paths:
            if key != BANK_ROWS:
                continue
            expanded = [f"{name}{BANK_SEP}{leaf.shape[0]}"]
        elif key in shard_keys:
            expanded = [f"{name}{shard_keys[key]}{leaf.shape[0]}"]
        elif key == A_SPLIT:
            expanded = [f"{name}{SPLIT_SEP}{k}" for k in range(leaf.shape[0])]
        elif len(getattr(leaf, "shape", ())) == 3:
            expanded = [f"{name}{GROUP_SEP}{k}" for k in range(leaf.shape[0])]
        else:
            expanded = [name]
        for n in expanded:
            if n not in names:
                names.append(n)
    return names


def discover_layers(model, *args, **kwargs) -> List[str]:
    """K-FAC layer names for ``model``, via an abstract (FLOP-free) init.

    The authoritative discovery: a layer is preconditionable iff it sows into
    the ``kfac_acts`` collection. Pass the same example args as ``init``.
    """
    from kfac_pytorch_tpu.models.layers import KFAC_ACTS

    # Shape-only trace: pin the dense A path — the fused Pallas kernel's
    # interpreter lowering (a grid scan) would bloat this throwaway jaxpr,
    # and both kernels sow identical shapes by construction.
    with factor_kernels.factor_kernel_scope("dense"):
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), *args, **kwargs)
        )
    return layer_names_from_capture(shapes.get(KFAC_ACTS, {}))


def _get_path(tree: PyTree, name: str) -> Any:
    node = tree
    for k in name.split("/"):
        node = node[k]
    return node


def layer_grads(grads: PyTree, names: List[str]) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Pull ``{'kernel': ..., 'bias'?: ...}`` grad dicts for each K-FAC layer.

    Grouped pseudo-layers get their group's output-channel slice of the
    kernel/bias grads (a grouped HWIO kernel's O axis is partitioned by
    group; its I axis is already per-group). Lens-split pseudo-layers get
    their ``features/S`` column slice of the dense kernel/bias grads.
    """
    counts = group_counts(names)
    s_counts = lens_counts(names)
    out = {}
    for name in names:
        sbase, form, _ = split_shard_name(name)
        if form is not None:
            # shard-lens layers: the whole (stacked) kernel grad rides under
            # the ONE shard name — slicing happens in factor space
            # (shardwise.lenses), where the shard blocks live
            node = _get_path(grads, sbase)
            entry = {"kernel": node["kernel"]}
            if form == "c" and "bias" in node:
                entry["bias"] = node["bias"]
            out[name] = entry
            continue
        bbase, bcount = split_bank_name(name)
        if bcount is not None:
            out[name] = {"kernel": _get_path(grads, bbase)["kernel"]}
            continue
        base, gi = split_group_name(name)
        si = None
        if gi is None:
            base, si = split_lens_name(name)
        node = _get_path(grads, base)
        if "embedding" in node:
            out[name] = {"embedding": node["embedding"]}
            continue
        kernel = node["kernel"]
        bias = node.get("bias")
        if gi is not None or si is not None:
            n_parts = counts[base] if gi is not None else s_counts[base]
            idx = gi if gi is not None else si
            co_g = kernel.shape[-1] // n_parts
            kernel = kernel[..., idx * co_g:(idx + 1) * co_g]
            if bias is not None:
                bias = bias[idx * co_g:(idx + 1) * co_g]
        entry = {"kernel": kernel}
        if bias is not None:
            entry["bias"] = bias
        out[name] = entry
    return out


def _unwrap_sown(leaf: Any) -> Any:
    # sow reduce_fn=overwrite still wraps the value in a 1-tuple.
    return leaf[-1] if isinstance(leaf, tuple) else leaf


@phase("kfac_capture")
def a_contribs(
    captured: PyTree,
    names: List[str],
    *,
    perturb_grads: PyTree = None,
    batch_averaged: bool = True,
) -> Dict[str, jnp.ndarray]:
    """Pull per-layer A-factor contributions from the ``kfac_acts`` collection.

    Grouped pseudo-layers read their row of the stacked ``[G, a, a]``
    contribution; lens-split pseudo-layers read their row of the ``a_lens``
    stack. A tied embedding/output head (its capture node carries
    ``g_tied``) additionally folds the decoder site's logit grad-output
    DIAGONAL into the [vocab] A diagonal — which needs the perturbation
    cotangents, so tied models must pass ``perturb_grads`` (and the same
    ``batch_averaged`` the G side uses).
    """
    counts = group_counts(names)
    # one pass over names (not one per grouped entry — that was O(N^2) at
    # trace time, ~500k split calls for ResNeXt-50's 512 pseudo-layers):
    # how many pseudo-entries of each grouped base the layer list carries
    present_counts: Dict[str, int] = {}
    for n in names:
        b, g = split_group_name(n)
        if g is not None:
            present_counts[b] = present_counts.get(b, 0) + 1
    s_counts = lens_counts(names)
    s_present: Dict[str, int] = {}
    for n in names:
        b, s = split_lens_name(n)
        if s is not None:
            s_present[b] = s_present.get(b, 0) + 1
    out = {}
    for name in names:
        shbase, form, count = split_shard_name(name)
        if form is not None:
            node = _get_path(captured, shbase)
            key = {"c": A_COL, "r": A_ROW, "e": A_MOE}[form]
            leaf = _unwrap_sown(node[key])
            if leaf.shape[0] != count:
                raise ValueError(
                    f"shard-lens layer {shbase!r}: name {name!r} declares "
                    f"{count} shards but the layer sowed a "
                    f"[{leaf.shape[0]}, ...] stack — rebuild the layer list "
                    "with capture.discover_layers"
                )
            if form == "c":
                # replicated A: the sow broadcasts one [a, a] contribution
                # into a [T, a, a] stack purely to carry T; read row 0
                out[name] = leaf[0]
            elif form == "r":
                out[name] = leaf  # per-shard A slices [T, a/T, a/T]
            else:
                # MoE: the UNNORMALIZED per-expert sums plus the token
                # fraction vector ride together so the comm plane pmeans
                # both (the weighted EMA normalizes after the reduction)
                out[name] = {
                    "S": leaf,
                    "f": _unwrap_sown(node[N_MOE]),
                }
            continue
        bbase, bcount = split_bank_name(name)
        if bcount is not None:
            node = _get_path(captured, bbase)
            if A_SHARED not in node:  # else the owner's statistic serves
                out[name] = _unwrap_sown(node[A_BANK])
            continue
        base, gi = split_group_name(name)
        if gi is None:
            sbase, si = split_lens_name(name)
            if si is not None:
                node = _get_path(captured, sbase)
                leaf = _unwrap_sown(node[A_SPLIT])
                if (
                    s_counts[sbase] != leaf.shape[0]
                    or s_present[sbase] != leaf.shape[0]
                ):
                    raise ValueError(
                        f"lens-split layer {sbase!r}: layer list carries "
                        f"{s_present[sbase]} pseudo-layers (max index "
                        f"{s_counts[sbase] - 1}) but the layer has "
                        f"{leaf.shape[0]} splits — keep all "
                        f"'{SPLIT_SEP}K' entries of a split layer together"
                    )
                out[name] = leaf[si]
                continue
        node = _get_path(captured, base)
        if A_SHARED in node:
            continue  # a sibling owns this input's A (KFAC(shared_a=...))
        leaf = _unwrap_sown(node[A_CONTRIB])
        if gi is None:
            if G_TIED in node:
                # Reduce lens: the decoder site's [vocab] grad-output
                # diagonal joins the embed site's token-frequency diagonal
                # — ONE shared statistic for the tied table.
                if perturb_grads is None:
                    raise ValueError(
                        f"layer {base!r} carries tied-head statistics "
                        f"({G_TIED!r}) but a_contribs was called without "
                        "perturb_grads — the decoder-site diagonal needs "
                        "the logit cotangent"
                    )
                tied_g = _get_path(perturb_grads, base)[OUT_TIED]
                out[name] = leaf + factors.compute_g_diag(
                    tied_g.astype(jnp.float32), batch_averaged=batch_averaged
                )
                continue
            if len(getattr(leaf, "shape", ())) == 3:
                # a stacked [G, a, a] contribution reached a non-expanded
                # name: KFAC was built with a plain layer list (e.g.
                # layers=None falling back to param paths) on a grouped
                # model — broadcasting the stack into the [a, a] running
                # average would corrupt factor state and surface later as
                # an opaque shape error
                raise ValueError(
                    f"layer {base!r} is a grouped conv (its A-contribution "
                    f"is a [{leaf.shape[0]}, a, a] stack) but was named "
                    "without group expansion; build KFAC with "
                    "layers=capture.discover_layers(model, ...) so grouped "
                    f"layers expand into '{GROUP_SEP}K' pseudo-layers"
                )
            out[name] = leaf
            continue
        # The sown [G, a, a] stack is the ground truth for G — enforce the
        # contract that a grouped layer's pseudo-entries are kept/dropped as
        # a COMPLETE set (a partial set would silently mis-derive the
        # output-channel split everywhere group_counts is used).
        present = present_counts[base]
        if counts[base] != leaf.shape[0] or present != leaf.shape[0]:
            raise ValueError(
                f"grouped layer {base!r}: layer list carries {present} "
                f"pseudo-layers (max index {counts[base] - 1}) but the "
                f"layer has {leaf.shape[0]} groups — keep all "
                f"'{GROUP_SEP}K' entries of a grouped layer together"
            )
        out[name] = leaf[gi]
    return out


@phase("kfac_capture")
def g_factors(
    perturb_grads: PyTree,
    names: List[str],
    batch_averaged: bool,
    *,
    captured: PyTree = None,
) -> Dict[str, jnp.ndarray]:
    """G factors from ∂L/∂(layer output) cotangents.

    Rank dispatch replaces the reference's isinstance dispatch
    (kfac/utils.py:144-153): rank-4 cotangents are conv outputs (NHWC),
    rank-2/3 are dense outputs (possibly with a time axis). Lens-split
    pseudo-layers compute their G from their ``features/S`` column slice of
    the fused cotangent (sliced with the same compute as an unfused layer —
    parity is bitwise). Tied heads fold the decoder site's sown query
    covariance (``g_tied``, in ``captured``) into the embed site's G.
    """
    counts = group_counts(names)
    # a grouped conv's output channels are partitioned by group; each
    # group's G factor is the covariance of its own slice — computed as ONE
    # batched contraction per base layer (512 sliced matmuls for ResNeXt-50
    # otherwise), then indexed per pseudo-layer
    stacked = {
        base: factors.compute_g_conv_grouped(
            _get_path(perturb_grads, base)[OUT_PERTURB].astype(jnp.float32),
            n_groups,
            batch_averaged=batch_averaged,
        )
        for base, n_groups in counts.items()
    }
    s_counts = lens_counts(names)
    out = {}
    for name in names:
        shbase, form, count = split_shard_name(name)
        if form is not None:
            node = _get_path(perturb_grads, shbase)
            if form == "c":
                # block-diagonal G: one covariance per kernel column shard
                out[name] = factors.compute_g_dense_sharded(
                    node[OUT_PERTURB].astype(jnp.float32),
                    count,
                    batch_averaged=batch_averaged,
                )
            elif form == "r":
                # row-sharded: every shard sees the same (psum'd) output
                # grad — ONE shared G factor
                out[name] = factors.compute_g_dense(
                    node[OUT_PERTURB].astype(jnp.float32),
                    batch_averaged=batch_averaged,
                )
            else:
                # MoE: the [.., E, m] perturbation cotangent is already
                # expert-masked by the top-1 routing
                out[name] = factors.compute_g_moe(
                    node[OUT_MOE].astype(jnp.float32),
                    batch_averaged=batch_averaged,
                )
            continue
        bbase, bcount = split_bank_name(name)
        if bcount is not None:
            if captured is None:
                raise ValueError(
                    f"expert bank {bbase!r}: g_factors needs captured= (the "
                    "bank's row counts ride in the kfac_acts collection)"
                )
            cap_node = _get_path(captured, bbase)
            out[name] = factors.compute_g_bank(
                _get_path(perturb_grads, bbase)[OUT_PERTURB].astype(jnp.float32),
                _unwrap_sown(cap_node[BANK_ROWS]),
                _unwrap_sown(cap_node[BANK_TOKENS]),
                batch_averaged=batch_averaged,
            )
            continue
        base, gi = split_group_name(name)
        if gi is not None:
            out[name] = stacked[base][gi]
            continue
        base, si = split_lens_name(name)
        g = _get_path(perturb_grads, base)[OUT_PERTURB]
        if si is not None:
            m = g.shape[-1] // s_counts[base]
            out[name] = factors.compute_g_dense(
                g[..., si * m:(si + 1) * m].astype(jnp.float32),
                batch_averaged=batch_averaged,
            )
            continue
        if g.ndim == 4:
            out[name] = factors.compute_g_conv(
                g.astype(jnp.float32), batch_averaged=batch_averaged
            )
        else:
            out[name] = factors.compute_g_dense(
                g.astype(jnp.float32), batch_averaged=batch_averaged
            )
            if captured is not None:
                cap_node = _get_path(captured, base)
                if G_TIED in cap_node:
                    out[name] = out[name] + _unwrap_sown(cap_node[G_TIED])
    return out


def bank_tape(
    tape: PyTree,
    perturb_grads: PyTree,
    names: List[str],
    shared_a: Dict[str, str],
) -> Dict[str, Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """``{bank name: (rows [M, a], ∂L/∂(bank output) [M, m], group sizes
    [E])}`` for every expert bank of ``names``, from the ``KFAC_TAPE``
    collection a step sowed and the gradient of its perturbations: the routed
    rows the bank multiplied (a ``shared_a`` bank reads its owner's), their
    cotangents and how many rows each expert has, each in its own dtype. The
    kernel's gradient is ``rowsᵀ · cotangents`` per expert, so the apply can
    work from these (ops/precondition.py::precondition_bank_rows)."""
    out = {}
    for name in names:
        bbase, bcount = split_bank_name(name)
        if bcount is None:
            continue
        owner = split_bank_name(shared_a.get(name, name))[0]
        out[name] = (
            _unwrap_sown(_get_path(tape, owner)[BANK_INPUT]),
            _get_path(perturb_grads, bbase)[OUT_PERTURB],
            _unwrap_sown(_get_path(tape, bbase)[BANK_ROWS]),
        )
    return out


def bank_perturbation_zeros(model, names: List[str], *args, **kwargs) -> PyTree:
    """:func:`perturbation_zeros` of the expert banks of ``names`` alone: what
    a step that captures nothing perturbs to read its banks' cotangents for
    :func:`bank_tape` (the other layers' outputs stay unperturbed)."""
    perts = perturbation_zeros(model, *args, **kwargs)
    out = {}
    for name in names:
        bbase, bcount = split_bank_name(name)
        if bcount is None:
            continue
        *parents, leaf = bbase.split("/")
        node = out
        for k in parents:
            node = node.setdefault(k, {})
        node[leaf] = _get_path(perts, bbase)
    return out


def factor_stat_tree(
    a_contribs: Dict[str, jnp.ndarray], g_stats: Dict[str, jnp.ndarray]
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Join the per-layer A and G stat dicts into ONE canonical pytree.

    The wire format of the factor-communication plane (parallel/comm.py):
    planning/flattening over the joint tree lets A and G leaves of different
    layers share buckets, and the fixed {"a": ..., "g": ...} framing keeps
    the flattened leaf order — and therefore the bucket layout — identical
    on every host. Handles every leaf shape capture produces: dense/conv
    ``[a, a]``/``[g, g]`` matrices and embedding diagonal-A ``[vocab]``
    vectors.
    """
    return {"a": a_contribs, "g": g_stats}


def split_factor_stat_tree(
    tree: Dict[str, Dict[str, jnp.ndarray]]
) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Inverse of :func:`factor_stat_tree`."""
    return tree["a"], tree["g"]


def grad_mats(
    lgrads: Dict[str, Dict[str, jnp.ndarray]], kernel_layout=frozenset()
) -> Dict[str, jnp.ndarray]:
    """Per-layer factor-space gradient matrices ``[out, in(+1)]``.

    MoE expert banks (``#eE`` names, rank-3 ``[E, a, m]`` kernels) become
    stacked ``[E, m, a]`` matrices — one factor-space mat per expert — but
    for the banks named in ``kernel_layout``, which keep their kernel's
    ``[E, a, m]`` (those preconditioned from their rows).
    """
    out = {}
    for name, g in lgrads.items():
        if name in kernel_layout:
            out[name] = g["kernel"]
        elif split_shard_name(name)[1] == "e" or split_bank_name(name)[1] is not None:
            out[name] = jnp.transpose(g["kernel"], (0, 2, 1))
        else:
            out[name] = factors.grads_to_mat(g)
    return out


def write_back(
    grads: PyTree, updates: Dict[str, jnp.ndarray], nu: jnp.ndarray,
    kernel_layout=frozenset(),
) -> PyTree:
    """Scatter ν-scaled preconditioned matrices back into the full grad pytree.

    Non-K-FAC leaves (BN, embeddings, ...) pass through untouched — parity
    with the reference, which only rewrites Linear/Conv2d grads
    (kfac_preconditioner.py:328-334). The banks named in ``kernel_layout``
    come in their kernel's ``[E, a, m]`` and are written as they come.
    """
    def _deep_copy(node):
        if isinstance(node, dict):
            return {k: _deep_copy(v) for k, v in node.items()}
        return node

    grads = _deep_copy(grads)
    grouped: Dict[str, Dict[int, jnp.ndarray]] = {}
    lensed: Dict[str, Dict[int, jnp.ndarray]] = {}
    for name, mat in updates.items():
        bbase, bcount = split_bank_name(name)
        if bcount is not None:
            # stacked [E, m, a] expert updates back to the [E, a, m] bank
            node = _get_path(grads, bbase)
            mat = mat * nu
            if name not in kernel_layout:
                mat = jnp.transpose(mat, (0, 2, 1))
            node["kernel"] = mat.astype(node["kernel"].dtype)
            continue
        shbase, form, _ = split_shard_name(name)
        if form is not None:
            node = _get_path(grads, shbase)
            if form == "e":
                # stacked [E, m, a] expert updates back to the [E, a, m] bank
                node["kernel"] = jnp.transpose(mat * nu, (0, 2, 1)).astype(
                    node["kernel"].dtype
                )
                continue
            # column/row-sharded dense: the update is a full-width
            # [m, a(+1)] mat (shard blocks were merged in factor space)
            new = factors.mat_to_grads(
                mat * nu, node["kernel"].shape, has_bias="bias" in node
            )
            node["kernel"] = new["kernel"].astype(node["kernel"].dtype)
            if "bias" in node:
                node["bias"] = new["bias"].astype(node["bias"].dtype)
            continue
        base, gi = split_group_name(name)
        if gi is not None:
            grouped.setdefault(base, {})[gi] = mat
            continue
        base, si = split_lens_name(name)
        if si is not None:
            lensed.setdefault(base, {})[si] = mat
            continue
        node = _get_path(grads, name)
        if "embedding" in node:
            # [features, vocab] mat back to the [vocab, features] table
            node["embedding"] = (mat * nu).T.astype(node["embedding"].dtype)
            continue
        kernel_shape = node["kernel"].shape
        new = factors.mat_to_grads(
            mat * nu, kernel_shape, has_bias="bias" in node
        )
        node["kernel"] = new["kernel"].astype(node["kernel"].dtype)
        if "bias" in node:
            node["bias"] = new["bias"].astype(node["bias"].dtype)
    for base, parts in grouped.items():
        # reassemble the per-group [co_g, a] updates along the O axis; the
        # complete-set contract (every group present, validated against the
        # sown stack in a_contribs) makes max-index+1 the group count
        node = _get_path(grads, base)
        kh, kw, ci_g, cout = node["kernel"].shape
        n_groups = max(parts) + 1
        if len(parts) != n_groups:
            raise ValueError(
                f"grouped layer {base!r}: updates carry {len(parts)} of "
                f"{n_groups} pseudo-layer groups — keep all '{GROUP_SEP}K' "
                "entries of a grouped layer together"
            )
        co_g = cout // n_groups
        has_bias = "bias" in node
        kernels, biases = [], []
        for gi in range(n_groups):
            sub = factors.mat_to_grads(
                parts[gi] * nu, (kh, kw, ci_g, co_g), has_bias
            )
            kernels.append(sub["kernel"])
            if has_bias:
                biases.append(sub["bias"])
        node["kernel"] = jnp.concatenate(kernels, axis=-1).astype(
            node["kernel"].dtype
        )
        if has_bias:
            node["bias"] = jnp.concatenate(biases).astype(node["bias"].dtype)
    for base, parts in lensed.items():
        # reassemble the per-split [m, a] updates along the fused kernel's
        # column axis — the exact inverse of layer_grads' column slicing
        node = _get_path(grads, base)
        cin, cout = node["kernel"].shape
        n_splits = max(parts) + 1
        if len(parts) != n_splits:
            raise ValueError(
                f"lens-split layer {base!r}: updates carry {len(parts)} of "
                f"{n_splits} pseudo-layer splits — keep all '{SPLIT_SEP}K' "
                "entries of a split layer together"
            )
        m = cout // n_splits
        has_bias = "bias" in node
        kernels, biases = [], []
        for si in range(n_splits):
            sub = factors.mat_to_grads(parts[si] * nu, (cin, m), has_bias)
            kernels.append(sub["kernel"])
            if has_bias:
                biases.append(sub["bias"])
        node["kernel"] = jnp.concatenate(kernels, axis=-1).astype(
            node["kernel"].dtype
        )
        if has_bias:
            node["bias"] = jnp.concatenate(biases).astype(node["bias"].dtype)
    return grads


def perturbation_zeros(model, *args, **kwargs) -> PyTree:
    """Zero perturbation pytree matching the model's layer outputs for a batch.

    Shapes depend on the batch, so this is evaluated per batch-shape via
    ``jax.eval_shape`` (no FLOPs); apply args/kwargs are passed through
    (e.g. ``train=True``).
    """
    from kfac_pytorch_tpu.models.layers import PERTURBATIONS

    # Dense-pinned for the same reason as discover_layers: this eval_shape
    # runs inside every captured step trace, and only shapes are kept.
    with factor_kernels.factor_kernel_scope("dense"):
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), *args, **kwargs)
        )
    perts = shapes[PERTURBATIONS]
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), perts)
