"""K-FAC-aware flax layers: Dense/Conv with curvature-statistics capture.

This replaces the reference's torch hook machinery
(``register_forward_pre_hook`` / ``register_backward_hook``,
kfac_preconditioner.py:146-153) — JAX has no module hooks, so capture is
explicit and functional:

* **A-side (input covariance):** each layer computes its own A-factor
  *contribution* from its input and ``sow``s it into the ``kfac_acts``
  collection. Sowing the [d, d] contribution instead of raw activations keeps
  capture memory O(d²) per layer instead of O(batch·d), and keeps the
  patch-extraction config (stride/padding/dilation) local to the layer — the
  optimizer never needs layer metadata. When ``kfac_acts`` is not listed as
  mutable in ``Module.apply``, the contribution is neither computed nor
  stored (capture is free on non-update steps).

* **G-side (grad-output covariance):** each layer adds a zero "perturbation"
  variable to its output (flax's ``Module.perturb``); differentiating the
  loss w.r.t. the ``perturbations`` collection yields exactly ∂L/∂(layer
  output). This is *cleaner* than the reference's deprecated
  ``register_backward_hook`` (which fires on pre-accumulation module grads);
  JAX gives the true output gradient.

Because both collections live at the same module path as the layer's params,
every per-layer artifact (kernel/bias grads, A contribution, output grad)
aligns on one path key — see ``capture.py``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from kfac_pytorch_tpu.ops import factor_kernels, factors, grouped

Dtype = Any
Padding = Union[str, int, Sequence[Tuple[int, int]]]

# Collection names (public constants — capture.py and train steps use them).
KFAC_ACTS = "kfac_acts"
PERTURBATIONS = "perturbations"
# An expert bank's tape (KFACBankDense): what the apply needs to precondition
# the bank from its rows, sown wherever a step program asks for it, captured
# or not.
KFAC_TAPE = "kfac_tape"
# Variable names inside a layer's path.
A_CONTRIB = "a"
OUT_PERTURB = "out"
# Expand-lens capture (fused QKV): a [S, a, a] stack of identical A
# contributions under its own name — rank-3 under A_CONTRIB already means
# grouped conv, and the G-side treatment differs (column slicing vs
# per-group slicing), so the split capture is a distinct variable.
A_SPLIT = "a_lens"
# Reduce-lens capture (tied embedding/output head): the decoder site's
# extra statistics, sown at the SAME module path as the embed site so the
# shared table accumulates both uses once.
G_TIED = "g_tied"
OUT_TIED = "out_tied"
# Shard-lens capture (kfac_pytorch_tpu/shardwise/): sharded-parameter dense
# layers sow distinct variables so capture.py can read the shard FORM (not
# just a count) off the key. A_COL is a broadcast [T, a, a] stack (replicated
# A, T carried in the leading dim); A_ROW is a genuine [T, a/T, a/T] stack of
# per-slice covariances; A_MOE is the [E, a, a] per-expert sum stack with
# N_MOE the [E] token-fraction vector alongside; OUT_MOE perturbs the dense
# [.., E, m] per-expert output so its cotangent is already expert-masked.
A_COL = "a_col"
A_ROW = "a_row"
A_MOE = "a_moe"
N_MOE = "n_moe"
OUT_MOE = "out_moe"
# Expert-bank capture (KFACBankDense): the rows arrive routed and sorted by
# expert, so the statistics are per-expert Grams over each expert's own rows.
# A_BANK is the [E, a, a] stack (plain 1/T scaling: an expert is a layer of
# its own whose unrouted rows are zero); BANK_ROWS the [E] row counts and
# BANK_TOKENS the token count T, which the G side needs beside the [M, m]
# cotangent. BANK_INPUT is the [M, a] rows themselves, as they come: with
# BANK_ROWS, the half of a bank's tape (KFAC_TAPE) that the apply reads
# beside that cotangent (ops/precondition.py::precondition_bank_rows).
# A_SHARED marks a layer that reads the same input as a sibling and keeps no
# A statistic of its own (KFAC(shared_a=...) names the owner).
A_BANK = "a_bank"
BANK_ROWS = "bank_rows"
BANK_TOKENS = "bank_tokens"
BANK_INPUT = "bank_input"
A_SHARED = "a_shared"
# Scalars a model reports beside the loss (routing load, ...): sown here,
# ``make_train_step`` puts them into the step's metrics.
STEP_SCALARS = "step_scalars"


def _overwrite(old: Any, new: Any) -> Any:
    """sow reduce_fn: keep only the latest value (no tuple accumulation)."""
    del old
    return new


def _normalize_padding(padding: Padding) -> Union[str, Tuple[Tuple[int, int], ...]]:
    if isinstance(padding, str):
        return padding
    if isinstance(padding, int):
        return ((padding, padding), (padding, padding))
    out = []
    for p in padding:
        out.append((p, p) if isinstance(p, int) else tuple(p))
    return tuple(out)


class _KFACLayer(nn.Module):
    """Shared capture plumbing for K-FAC-aware layers."""

    def _capturing(self) -> bool:
        return self.is_initializing() or self.is_mutable_collection(KFAC_ACTS)

    def _sow_a(self, contrib_fn: Callable[[], jnp.ndarray]) -> None:
        # Only trace the (expensive) factor contribution when capturing; on
        # plain steps the matmul never enters the program.
        if self._capturing():
            self.sow(KFAC_ACTS, A_CONTRIB, contrib_fn(), reduce_fn=_overwrite)

    def _sow_a_shared(self) -> None:
        # a sibling layer owns the A statistic of this input: leave a mark so
        # that discovery still finds the layer, and multiply nothing
        if self._capturing():
            self.sow(
                KFAC_ACTS, A_SHARED, jnp.zeros((0,), jnp.float32),
                reduce_fn=_overwrite,
            )

    def _maybe_perturb(self, y: jnp.ndarray, name: str = OUT_PERTURB) -> jnp.ndarray:
        # Gate so the model also applies cleanly WITHOUT a perturbations
        # collection (eval / plain SGD steps): flax's Module.perturb would
        # require the collection to exist.
        if self.is_initializing() or self.has_variable(PERTURBATIONS, name):
            return self.perturb(name, y)
        return y


class KFACDense(_KFACLayer):
    """Dense layer (``y = x @ kernel + bias``) with K-FAC capture.

    Drop-in for ``flax.linen.Dense``; the preconditionable analog of the
    reference's ``nn.Linear`` handling (kfac/utils.py:119-128, 172-183).
    Inputs of rank > 2 (e.g. ``[B, T, d]``) are supported — factor math
    flattens leading axes, matching how the reference's LM decoder flattens
    tokens.

    ``lens_splits = S > 1`` turns on the expand Kronecker lens for fused
    multi-head projections (e.g. one [m, 3m] QKV matmul): the layer is
    captured as S independent ``name#sK`` pseudo-layers, each with the
    shared input-side A factor and its own ``features/S``-side G factor.
    The forward matmul stays fused; only the curvature model splits —
    refresh cost drops from one (3m)³ eigh to three m³ eighs (~9×) and the
    factors land in existing shape buckets (*KFAC for Modern Neural Network
    Architectures*, arxiv 2311.00636).
    """

    features: int
    use_bias: bool = True
    lens_splits: int = 1
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()
    # ``a_shared``: a sibling layer reads the same input and owns its A
    # statistic (``KFAC(shared_a={this: sibling})``); nothing is multiplied
    # here. ``precision`` is the forward product's (None: the default).
    a_shared: bool = False
    precision: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.a_shared and (self.lens_splits > 1 or self.use_bias):
            raise ValueError(
                "a_shared layers are plain bias-free projections (the owner's "
                "A factor has no bias column and no lens split)"
            )
        if self.lens_splits > 1 and self.features % self.lens_splits:
            raise ValueError(
                f"lens_splits={self.lens_splits} must divide "
                f"features={self.features}"
            )
        kernel = self.param(
            "kernel", self.kernel_init, (x.shape[-1], self.features), self.param_dtype
        )
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,), self.param_dtype)
        else:
            bias = None

        if self.lens_splits > 1:
            # Expand lens (fused QKV): the layer is S narrow projections
            # sharing one input, so every pseudo-layer's A factor is the
            # SAME matrix — sow it once, broadcast-stacked [S, a, a] so
            # capture.py can read S off the leaf and expand ``name#sK``
            # pseudo-layers. XLA CSEs the broadcast; no extra matmul.
            if self._capturing():
                contrib = factors.compute_a_dense(
                    x.astype(jnp.float32), has_bias=self.use_bias
                )
                self.sow(
                    KFAC_ACTS,
                    A_SPLIT,
                    jnp.broadcast_to(
                        contrib[None], (self.lens_splits,) + contrib.shape
                    ),
                    reduce_fn=_overwrite,
                )
        elif self.a_shared:
            self._sow_a_shared()
        else:
            self._sow_a(
                lambda: factors.compute_a_dense(
                    x.astype(jnp.float32), has_bias=self.use_bias
                )
            )

        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        y = jnp.matmul(x, kernel, precision=self.precision)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return self._maybe_perturb(y)


class KFACBankDense(_KFACLayer):
    """One projection of a bank of experts, over rows already routed.

    ``rows`` is ``[M, a]``: the token-expert pairs' inputs sorted by expert,
    the held experts' groups first and in order; ``group_sizes`` ``[E]`` says
    how many rows each held expert has (rows past their sum belong to no held
    expert: their output is zero and they count for nothing). The product is
    a grouped one (``ops/grouped.py``), so the work follows the rows
    routed and no ``[T, E, .]`` tensor exists. ``kernel`` is ``[E, a, m]``,
    no bias.

    Curvature: E layers of their own (Martens & Grosse's layer-wise
    independence; ``benchmarks/reference/kfac_sgd.py``'s ``bank`` kind):
    ``A_e = (1/T) sum_{t in e} x_t x_t^T`` and, from the perturbation's
    cotangent, ``G_e = T sum_{t in e} g_t g_t^T`` with ``T = n_tokens``,
    running averages with the plain decay. Captured as ONE ``name#bE`` layer
    whose factors stay stacked ``[E, ., .]``. Where ``KFAC_TAPE`` is mutable
    the group sizes and the rows it owns are sown there too, so that the apply
    can precondition the bank from them.
    """

    features: int
    num_experts: int
    a_shared: bool = False
    # ``kfac=False`` leaves the bank to plain SGD: the grouped product alone,
    # nothing sown and no perturbation
    kfac: bool = True
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal(batch_axis=(0,))

    @nn.compact
    def __call__(
        self, rows: jnp.ndarray, group_sizes: jnp.ndarray, n_tokens: int
    ) -> jnp.ndarray:
        kernel = self.param(
            "kernel", self.kernel_init,
            (self.num_experts, rows.shape[-1], self.features), self.param_dtype,
        )
        if self.kfac and self._capturing():
            self.sow(KFAC_ACTS, BANK_ROWS, group_sizes, reduce_fn=_overwrite)
            self.sow(
                KFAC_ACTS, BANK_TOKENS, jnp.full((), n_tokens, jnp.int32),
                reduce_fn=_overwrite,
            )
            if self.a_shared:
                self._sow_a_shared()
            else:
                self.sow(
                    KFAC_ACTS, A_BANK,
                    factors.compute_a_bank(
                        rows.astype(jnp.float32), group_sizes, n_tokens
                    ),
                    reduce_fn=_overwrite,
                )
        if self.kfac and not self.is_initializing() and self.is_mutable_collection(KFAC_TAPE):
            self.sow(KFAC_TAPE, BANK_ROWS, group_sizes, reduce_fn=_overwrite)
            if not self.a_shared:
                self.sow(KFAC_TAPE, BANK_INPUT, rows, reduce_fn=_overwrite)
        rows, kernel = nn.dtypes.promote_dtype(rows, kernel, dtype=self.dtype)
        y = grouped.grouped_matmul(rows, kernel, group_sizes)
        return self._maybe_perturb(y) if self.kfac else y


class KFACShardedDense(_KFACLayer):
    """Dense layer whose kernel is SHARDED over a tensor-parallel axis, with
    per-shard K-FAC capture (kfac_pytorch_tpu/shardwise/).

    The compute is an ordinary ``y = x @ kernel (+ bias)`` — GSPMD shards it
    when the trainer places the kernel with
    ``shardwise.lm_param_shardings`` over a mesh with a genuine
    compute-sharded ``tensor`` axis (``parallel.mesh.data_fsdp_tensor_mesh``).
    What changes is the CURVATURE model (arxiv 2311.00636 lens algebra):

    * ``sharding="column"`` (kernel ``[a, m]`` split along m): every shard
      reads the full input, so A is replicated; the shards' outputs are
      disjoint, so G is exactly block-diagonal — captured as a ``[T, m/T,
      m/T]`` stack, preconditioned shard-locally with ZERO extra collectives
      on the tensor axis (scripts/check_collective_count.py pins this).
    * ``sharding="row"`` (kernel split along a): each shard reads its own
      input slice → per-shard A stack ``[T, a/T, a/T]``; the output-grad is
      shared (the forward's psum), so ONE G factor. ``use_bias`` must stay
      False — a row-sharded bias is not attributable to one input shard.

    Captured as ONE ``name#c{T}``/``name#r{T}`` layer whose factors stay
    stacked (capture.split_shard_name), unlike the per-index ``#sK``
    expansion of the fused-QKV lens.
    """

    features: int
    shards: int
    sharding: str = "column"
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.sharding not in ("column", "row"):
            raise ValueError(
                f"sharding={self.sharding!r} must be 'column' or 'row'"
            )
        if self.shards < 1:
            raise ValueError(f"shards={self.shards} must be >= 1")
        if self.sharding == "column":
            if self.features % self.shards:
                raise ValueError(
                    f"column sharding needs shards={self.shards} to divide "
                    f"features={self.features}"
                )
        else:
            if x.shape[-1] % self.shards:
                raise ValueError(
                    f"row sharding needs shards={self.shards} to divide the "
                    f"input width {x.shape[-1]}"
                )
            if self.use_bias:
                raise ValueError(
                    "row-sharded layers cannot carry a bias: the bias is "
                    "not attributable to one input shard — set "
                    "use_bias=False"
                )
        kernel = self.param(
            "kernel", self.kernel_init, (x.shape[-1], self.features), self.param_dtype
        )
        if self.use_bias:
            bias = self.param(
                "bias", self.bias_init, (self.features,), self.param_dtype
            )
        else:
            bias = None

        if self._capturing():
            if self.sharding == "column":
                # replicated A, broadcast-stacked [T, a(+1), a(+1)] so
                # capture.py reads T off the leading dim (XLA CSEs the
                # broadcast — no extra matmul, like the lens-split sow)
                contrib = factors.compute_a_dense(
                    x.astype(jnp.float32), has_bias=self.use_bias
                )
                self.sow(
                    KFAC_ACTS,
                    A_COL,
                    jnp.broadcast_to(
                        contrib[None], (self.shards,) + contrib.shape
                    ),
                    reduce_fn=_overwrite,
                )
            else:
                self.sow(
                    KFAC_ACTS,
                    A_ROW,
                    factors.compute_a_row_sharded(
                        x.astype(jnp.float32), self.shards
                    ),
                    reduce_fn=_overwrite,
                )

        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        y = jnp.matmul(x, kernel)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return self._maybe_perturb(y)


class KFACMoE(_KFACLayer):
    """Toy mixture-of-experts bank (top-1 routing) with per-expert K-FAC.

    ``E`` experts share one ``[E, a, m]`` kernel bank; a bias-free router
    picks one expert per token (its gate probability scales the output, so
    the router itself trains by plain SGD through the gate). The curvature
    model is the MoE expert lens: per-expert A/G factor stacks whose EMAs
    are token-count-weighted (experts that saw no tokens keep their history
    untouched) — maintained by the preconditioner from the sown
    UNNORMALIZED per-expert sums plus the ``[E]`` token-fraction vector, so
    every sown leaf stays linear in per-token contributions and the
    cross-replica pmean is exact.

    The ``[tokens, experts]`` dispatch one-hot never densifies: fractions
    ride the sparse embedding-bincount kernel
    (``dispatch_compute_a_moe``), and the per-expert covariance sums mask
    with [N] booleans (``factors.compute_a_moe``). Captured as ONE
    ``name#e{E}`` layer.
    """

    features: int
    num_experts: int
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.num_experts < 2:
            raise ValueError(
                f"num_experts={self.num_experts} must be >= 2 (use KFACDense "
                "for a single expert)"
            )
        a = x.shape[-1]
        lead = x.shape[:-1]
        xf = x.reshape(-1, a)
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (self.num_experts, a, self.features),
            self.param_dtype,
        )
        logits = nn.Dense(
            self.num_experts, use_bias=False, name="router",
            param_dtype=self.param_dtype,
        )(xf)
        idx = jnp.argmax(logits, axis=-1)  # [N] top-1 expert ids
        gate = jnp.take_along_axis(
            jax.nn.softmax(logits, axis=-1), idx[:, None], axis=-1
        )  # [N, 1]

        if self._capturing():
            self.sow(
                KFAC_ACTS,
                A_MOE,
                factors.compute_a_moe(
                    xf.astype(jnp.float32), idx, self.num_experts
                ),
                reduce_fn=_overwrite,
            )
            self.sow(
                KFAC_ACTS,
                N_MOE,
                factor_kernels.dispatch_compute_a_moe(idx, self.num_experts),
                reduce_fn=_overwrite,
            )

        xf, kernel = nn.dtypes.promote_dtype(xf, kernel, dtype=self.dtype)
        # dense per-expert outputs [N, E, m] (toy scale); perturbing THIS
        # tensor makes the cotangent expert-masked for free: only the
        # selected expert's row feeds y, so ∂L/∂h is zero elsewhere
        h = jnp.einsum("na,eam->nem", xf, kernel)
        h = self._maybe_perturb(h, OUT_MOE)
        sel = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0, :]
        y = gate.astype(sel.dtype) * sel
        return y.reshape(lead + (self.features,))


class KFACEmbed(_KFACLayer):
    """Embedding lookup (``y = table[ids]``) with K-FAC capture.

    Drop-in for ``flax.linen.Embed``. BEYOND-reference capability: the
    reference preconditions only Linear/Conv2d, leaving LM embeddings to
    plain SGD (``known_modules``, kfac_preconditioner.py:103). A lookup is a
    dense layer over one-hot inputs, whose input covariance is exactly the
    diagonal of token frequencies — the A factor is a [vocab] vector
    (ops/factors.py::compute_a_embed) and its eigenbasis is the identity, so
    embedding K-FAC costs one [features, features] G factor plus elementwise
    work on the vocab axis.
    """

    num_embeddings: int
    features: int
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    embedding_init: Callable = nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", out_axis=0
    )

    def setup(self):
        # setup-style (not @nn.compact) so the table is shared between
        # __call__ and attend — the reduce lens for tied embedding/output
        # heads needs both methods on one module instance.
        self.embedding = self.param(
            "embedding",
            self.embedding_init,
            (self.num_embeddings, self.features),
            self.param_dtype,
        )

    def __call__(self, ids: jnp.ndarray) -> jnp.ndarray:
        # Diagonal-A capture routes through the factor-kernel dispatcher:
        # scatter-add bincount by default, the fused Pallas token-gather
        # kernel when the train step opened a "pallas" scope.
        self._sow_a(
            lambda: factor_kernels.dispatch_compute_a_embed(
                ids, self.num_embeddings
            )
        )
        (table,) = nn.dtypes.promote_dtype(self.embedding, dtype=self.dtype)
        y = jnp.take(table, ids, axis=0)
        return self._maybe_perturb(y)

    def attend(self, query: jnp.ndarray) -> jnp.ndarray:
        """Tied decoder head: ``logits = query @ tableᵀ`` with reduce-lens
        capture.

        Drop-in for ``flax.linen.Embed.attend``. The decoder site reuses the
        shared table as a [features, vocab] projection, so its Kronecker
        statistics fold into the embed site's factors ONCE (weight-shared
        "reduce" setting, arxiv 2311.00636): the query input covariance
        (sown here as ``g_tied``) adds to the [features] G side, and the
        logit grad-output diagonal (via the ``out_tied`` perturbation,
        reduced in capture.py) adds to the [vocab] diagonal A side.
        """
        if self._capturing():
            self.sow(
                KFAC_ACTS,
                G_TIED,
                factors.compute_a_dense(
                    query.astype(jnp.float32), has_bias=False
                ),
                reduce_fn=_overwrite,
            )
        query, table = nn.dtypes.promote_dtype(
            query, self.embedding, dtype=self.dtype
        )
        y = jnp.matmul(query, table.T)
        return self._maybe_perturb(y, OUT_TIED)


class KFACConv(_KFACLayer):
    """2-D convolution (NHWC/HWIO) with K-FAC capture.

    Drop-in for ``flax.linen.Conv`` (2-D case); the preconditionable analog
    of the reference's ``nn.Conv2d`` handling (kfac/utils.py:107-117,
    155-170). The A-factor contribution runs the same patch extraction the
    conv itself uses, so stride/padding/dilation stay consistent by
    construction.

    ``feature_group_count > 1`` (grouped conv, e.g. ResNeXt) is captured as
    G independent Kronecker pairs — the sown A contribution is stacked
    ``[G, a, a]`` and capture.py expands the layer into ``name#gK``
    pseudo-layers. BEYOND-reference: the reference cannot precondition
    grouped convs (its im2col factor shape is inconsistent for groups > 1).
    """

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: Padding = "SAME"
    kernel_dilation: Tuple[int, int] = (1, 1)
    feature_group_count: int = 1
    use_bias: bool = False
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        kh, kw = self.kernel_size
        groups = self.feature_group_count
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (kh, kw, x.shape[-1] // groups, self.features),
            self.param_dtype,
        )
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,), self.param_dtype)
        else:
            bias = None

        padding = _normalize_padding(self.padding)
        # Conv A contributions route through the factor-kernel dispatcher:
        # dense im2col oracle by default, the fused Pallas patch-covariance
        # kernel when the train step opened a "pallas" scope
        # (KFAC(factor_kernel=...), ops/factor_kernels.py).
        if groups == 1:
            self._sow_a(
                lambda: factor_kernels.dispatch_compute_a_conv(
                    x.astype(jnp.float32),
                    self.kernel_size,
                    self.strides,
                    padding,
                    has_bias=self.use_bias,
                    kernel_dilation=self.kernel_dilation,
                )
            )
        else:
            self._sow_a(
                lambda: factor_kernels.dispatch_compute_a_conv_grouped(
                    x.astype(jnp.float32),
                    groups,
                    self.kernel_size,
                    self.strides,
                    padding,
                    has_bias=self.use_bias,
                    kernel_dilation=self.kernel_dilation,
                )
            )

        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        y = lax.conv_general_dilated(
            x,
            kernel,
            window_strides=self.strides,
            padding=padding,
            rhs_dilation=self.kernel_dilation,
            feature_group_count=groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return self._maybe_perturb(y)
