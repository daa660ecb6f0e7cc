"""Sparse-expert decoder LM with latent attention (the ``glm4_moe_lite``
family: GLM-4.7-Flash), on the training path, for one expert-parallel rank.

Per block, pre-norm, no bias anywhere::

    h = RMSNorm(x)                                       float32, eps 1e-5
    c_q = RMSNorm(h W_qa);  q = c_q W_qb                 heads of [nope | rope]
    [c_kv | k_r] = h W_kva; c_kv = RMSNorm(c_kv);  k_rope = RoPE(k_r), one for all heads
    c_kv W_kvb -> per head [k_nope | v]
    q = [q_nope | RoPE(q_rope)],  k = [k_nope | k_rope]
    o = softmax(q k^T / sqrt(qk_head_dim) + causal) v;   x += concat_heads(o) W_o

    h = RMSNorm(x)
    s = sigmoid(h W_r) over ALL n_routed_experts         float32 at highest
    S = top_k(s);  w_e = routed_scaling_factor * s_e / sum_{j in S} s_j
    x += sum_{e in S and held} w_e MLP_e(h) + MLP_shared(h),   MLP(h) = (silu(h W_g) * h W_u) W_d

The first ``first_k_dense`` blocks have one gated MLP of ``intermediate_size``
in place of the experts. End: RMSNorm, ``logits = x W_head``.

**One rank of an expert-parallel group.** ``held = (first, count)`` names the
routed experts whose weights live here. Every token is routed over all
``n_routed_experts``; the token-expert pairs are sorted by expert and only
the held groups are multiplied (``ops/grouped.py``), so the work follows
the rows routed, no row is dropped at any load, and what the absent experts
would add is left out: nothing stands in for the other ranks or their
traffic. Summed over the ranks' ``held`` ranges (the shared expert counted
once) the layer is the uncut one.

**K-FAC** covers every projection whose two factor sides are at most
``kfac_max_side``: the latent down-projections ``q_a`` / ``kv_a`` (one A: they
read one input), the router and the shared expert's gate / up (one A), the
shared ``down``, and the expert banks ``gate`` / ``up`` (one A stack) and
``down`` as :class:`~kfac_pytorch_tpu.models.layers.KFACBankDense`. The
up-projections out of the latents, the output projection, a dense block's
MLP, embedding, head and norms train by SGD, and so do the groups named in
``kfac_exclude`` (``"dense_layers"``: the leading dense blocks' projections,
``"shared_expert"``, ``"down_banks"``), which a configuration leaves out where
the chip's memory says so. :func:`shared_inputs` gives ``KFAC(shared_a=...)``
for a discovered layer list.

Phases (observability/phases.py), entered here and nowhere else:
``attention`` (rotary, the latent reshapes and the attention call),
``moe_route`` (router product, top-k, sort, gather, combine), ``moe_experts``
(the grouped products). Step scalars beside the loss: ``moe_held_rows``,
``moe_load_max_over_mean``, ``moe_dropped_rows``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from kfac_pytorch_tpu import capture
from kfac_pytorch_tpu.models.layers import STEP_SCALARS, KFACBankDense, KFACDense
from kfac_pytorch_tpu.observability.phases import phase
from kfac_pytorch_tpu.parallel.context import full_attention

AttentionFn = Callable[..., jnp.ndarray]  # (q, k, v, causal=...) -> out


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        return xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps) * scale


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions over the last axis of ``[B, T, ..., d]`` (half-split
    pairing: dim i turns with dim i + d/2), position = index along axis 1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _linear(features: int, name: str) -> nn.Dense:
    """An SGD-trained projection (no K-FAC capture)."""
    return nn.Dense(features, use_bias=False, name=name)


class LatentAttention(nn.Module):
    """Multi-head latent attention, unabsorbed (training form; no cache)."""

    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    eps: float
    kfac_max_side: int  # 0: every projection trains by SGD
    attention_fn: AttentionFn = full_attention

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        nh, nope, rot, vd = self.n_heads, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        small = lambda *sides: max(sides) <= self.kfac_max_side
        down = lambda feats, name, shared: (
            KFACDense(feats, use_bias=False, a_shared=shared, name=name)
            if small(d, feats) else _linear(feats, name))
        c_q = RMSNorm(self.eps, name="q_norm")(down(self.q_lora_rank, "q_a", False)(h))
        kv = down(self.kv_lora_rank + rot, "kv_a", small(d, self.q_lora_rank))(h)
        c_kv = RMSNorm(self.eps, name="kv_norm")(kv[..., : self.kv_lora_rank])
        q = _linear(nh * (nope + rot), "q_b")(c_q)
        kvb = _linear(nh * (nope + vd), "kv_b")(c_kv)
        with phase("attention"):
            q = q.reshape(b, t, nh, nope + rot)
            kvb = kvb.reshape(b, t, nh, nope + vd)
            k_rope = rope(kv[..., self.kv_lora_rank:].reshape(b, t, 1, rot), self.rope_theta)
            q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], self.rope_theta)], axis=-1)
            k = jnp.concatenate(
                [kvb[..., :nope], jnp.broadcast_to(k_rope, (b, t, nh, rot))], axis=-1)
            o = self.attention_fn(q, k, kvb[..., nope:], causal=True)
            o = o.reshape(b, t, nh * vd)
        return _linear(d, "o")(o)


class GatedMLP(nn.Module):
    """``(silu(h W_g) * h W_u) W_d``; under K-FAC where its sides allow, the
    gate and up projections sharing one A."""

    width: int
    kfac_max_side: int

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]
        kfac = max(d, self.width) <= self.kfac_max_side
        proj = lambda feats, name, shared=False: (
            KFACDense(feats, use_bias=False, a_shared=shared, name=name)
            if kfac else _linear(feats, name))
        act = jax.nn.silu(proj(self.width, "gate")(h)) * proj(self.width, "up", True)(h)
        return proj(d, "down")(act)


def route(scores: jnp.ndarray, k: int, scale: float, held: Tuple[int, int]):
    """Top-``k`` routing of ``scores`` ``[T, n_routed]`` for the rank that
    holds experts ``held = (first, count)``: ``(token, weight, group_sizes)``
    with ``token`` ``[T*k]`` the pairs' token indices sorted by expert, the
    held experts' groups first and in order, ``weight`` ``[T*k]`` the pairs'
    routing weights (``scale * s_e / sum of the token's chosen``; zero for a
    pair on an expert not held) and ``group_sizes`` ``[count]``."""
    first, count = held
    t = scores.shape[0]
    top, chosen = lax.top_k(scores, k)
    weight = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    local = chosen.reshape(-1) - first
    is_held = (local >= 0) & (local < count)
    group = jnp.where(is_held, local, count)  # the pairs of absent experts go last
    order = jnp.argsort(group, stable=True)
    token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)[order]
    weight = jnp.where(is_held, weight.reshape(-1), 0.0)[order]
    group_sizes = jnp.bincount(group, length=count + 1)[:count].astype(jnp.int32)
    return token, weight, group_sizes


class ExpertMLP(nn.Module):
    """The expert layer's MLP half for one expert-parallel rank (module
    docstring): router over all experts, grouped products over the held
    ones, the shared expert."""

    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    moe_intermediate_size: int
    held: Tuple[int, int]
    kfac_max_side: int
    kfac_exclude: Tuple[str, ...] = ()

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        n, (first, count) = b * t, self.held
        if not (0 <= first and 0 < count and first + count <= self.n_routed_experts):
            raise ValueError(f"held={self.held} is not a range of the {self.n_routed_experts} routed experts")
        kfac = max(d, self.moe_intermediate_size) <= self.kfac_max_side
        if not kfac:
            raise ValueError("expert banks are K-FAC layers: kfac_max_side below their sides")
        hf = h.reshape(n, d).astype(jnp.float32)
        width = self.moe_intermediate_size
        dense = lambda feats, name, shared=False, **kw: KFACDense(
            feats, use_bias=False, a_shared=shared, name=name, **kw)
        kfac_shared = "shared_expert" not in self.kfac_exclude
        shared_proj = lambda feats, name, shared=False: (
            dense(feats, name, shared) if kfac_shared else _linear(feats, name))
        bank = lambda feats, name, shared=False, kfac=True: KFACBankDense(
            feats, count, a_shared=shared, kfac=kfac, name=name)
        with phase("moe_route"):
            # float32 at highest, as the published implementations compute the
            # router; it reads the shared gate's input and leaves the A to it
            logits = dense(self.n_routed_experts, "router", kfac_shared, precision=lax.Precision.HIGHEST)(hf)
            token, weight, group_sizes = route(
                jax.nn.sigmoid(logits), self.num_experts_per_tok, self.routed_scaling_factor, self.held)
            rows = jnp.take(hf, token, axis=0)
        with phase("moe_experts"):
            act = jax.nn.silu(bank(width, "gate")(rows, group_sizes, n)) \
                * bank(width, "up", True)(rows, group_sizes, n)
            out = bank(d, "down", kfac="down_banks" not in self.kfac_exclude)(act, group_sizes, n)
        with phase("moe_route"):
            routed = jnp.zeros((n, d), out.dtype).at[token].add(out * weight[:, None].astype(out.dtype))
        # the shared expert: its gate owns the A of this layer's input, which
        # the router and its up projection read too
        act = jax.nn.silu(shared_proj(width, "shared_gate")(hf)) * shared_proj(width, "shared_up", True)(hf)
        y = routed + shared_proj(d, "shared_down")(act)
        held_rows = jnp.sum(group_sizes).astype(jnp.float32)
        scalars = {
            "moe_held_rows": held_rows,
            "moe_load_max_over_mean": jnp.max(group_sizes) * count / jnp.maximum(held_rows, 1.0),
            # every pair on a held expert has a row of its own in ``rows``
            "moe_dropped_rows": jnp.zeros((), jnp.float32),
        }
        return y.reshape(b, t, d), scalars


@dataclasses.dataclass(frozen=True)
class GLMMoELiteConfig:
    """The sizes of one rank's cut of a ``glm4_moe_lite`` model."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    held: Tuple[int, int]  # (first, count) of the routed experts this rank holds
    kfac_max_side: int  # K-FAC on projections whose factor sides are at most this
    kfac_exclude: Tuple[str, ...] = ()  # of "dense_layers", "shared_expert", "down_banks": left to SGD


class Block(nn.Module):
    cfg: GLMMoELiteConfig
    dense: bool
    attention_fn: AttentionFn = full_attention

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = self.cfg
        unknown = set(c.kfac_exclude) - {"dense_layers", "shared_expert", "down_banks"}
        if unknown:
            raise ValueError(f"kfac_exclude names no group of projections: {sorted(unknown)}")
        max_side = 0 if self.dense and "dense_layers" in c.kfac_exclude else c.kfac_max_side
        h = RMSNorm(c.rms_norm_eps, name="norm_attn")(x)
        x = x + LatentAttention(
            c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.rope_theta, c.rms_norm_eps, max_side,
            attention_fn=self.attention_fn, name="attn")(h)
        h = RMSNorm(c.rms_norm_eps, name="norm_mlp")(x)
        if self.dense:
            return x + GatedMLP(c.intermediate_size, max_side, name="mlp")(h), None
        y, scalars = ExpertMLP(
            c.n_routed_experts, c.num_experts_per_tok, c.routed_scaling_factor,
            c.moe_intermediate_size, tuple(c.held), c.kfac_max_side, tuple(c.kfac_exclude), name="mlp")(h)
        return x + y, scalars


# how the expert layers' scalars become the step's: rows add up, the load is the worst layer's
_STEP_SCALARS = {"moe_held_rows": sum, "moe_load_max_over_mean": lambda v: jnp.max(jnp.stack(v)),
                 "moe_dropped_rows": sum}


class GLMMoELite(nn.Module):
    cfg: GLMMoELiteConfig
    attention_fn: AttentionFn = full_attention
    # recompute each block's forward pass in the backward pass (jax.checkpoint
    # via nn.remat): the sown statistics are outputs of the first pass, which
    # the backward pass does not need, so none is multiplied twice
    remat: bool = True

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        x = nn.Embed(c.vocab_size, c.hidden_size, name="embed")(tokens)
        block_cls = nn.remat(Block, static_argnums=(2,)) if self.remat else Block
        per_layer = []
        for i in range(c.num_hidden_layers):
            x, scalars = block_cls(c, i < c.first_k_dense, self.attention_fn, name=f"layer_{i}")(x, train)
            per_layer += [scalars] if scalars is not None else []
        for name, combine in _STEP_SCALARS.items() if per_layer else ():
            self.sow(STEP_SCALARS, name, combine([s[name] for s in per_layer]),
                     reduce_fn=lambda old, new: new)
        x = RMSNorm(c.rms_norm_eps, name="norm_f")(x)
        return _linear(c.vocab_size, "head")(x)


def get_model(attention_fn: AttentionFn = full_attention, remat: bool = True, **sizes) -> GLMMoELite:
    return GLMMoELite(GLMMoELiteConfig(**sizes), attention_fn=attention_fn, remat=remat)


def shared_inputs(layers: List[str]) -> Dict[str, str]:
    """``KFAC(shared_a=...)`` for a layer list discovered from this model:
    ``kv_a`` reads ``q_a``'s input; the router and the shared expert's ``up``
    read its ``gate``'s; an expert bank's (or a dense MLP's) ``up`` reads its
    ``gate``'s."""
    owner_of = {"kv_a": "q_a", "router": "shared_gate", "shared_up": "shared_gate", "up": "gate"}
    by_base = {capture.layer_base(n): n for n in layers}
    out = {}
    for base, name in by_base.items():
        parent, _, leaf = base.rpartition("/")
        owner = by_base.get(f"{parent}/{owner_of[leaf]}") if leaf in owner_of else None
        if owner is not None:
            out[name] = owner
    return out
