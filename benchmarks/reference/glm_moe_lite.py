"""Plain ``glm4_moe_lite`` decoder LM (GLM-4.7-Flash: latent attention, a
sigmoid top-k router over sparse gated-SiLU experts beside one shared expert,
RMSNorm, rotary positions) forward pass and loss in ``jax.numpy``, for the
output check: one expert-parallel rank's share, as the configuration's file
cuts it. Nothing of the program is imported. Parameter names are those of
the tree the benchmark's weights come in.

Per block, pre-norm, no bias (equations as the configuration's ``assumed``
states them; the published model's ``config.json`` gives the sizes)::

    h = RMSNorm(x)
    c_q = RMSNorm(h W_qa);  q = c_q W_qb, per head [q_nope | q_rope]
    [c_kv | k_r] = h W_kva;  c_kv = RMSNorm(c_kv);  c_kv W_kvb, per head [k_nope | v]
    q = [q_nope | RoPE(q_rope)];  k = [k_nope | RoPE(k_r)], the rotary part one for all heads
    x += concat_heads(softmax(q k^T / sqrt(d_qk) + causal) v) W_o
    h = RMSNorm(x)
    s = sigmoid(h W_r) over all the published experts; S = top_k(s)
    w_e = routed_scaling_factor * s_e / sum_{j in S} s_j
    x += sum_{e in S, e held here} w_e MLP_e(h) + MLP_shared(h);  MLP(h) = (silu(h W_g) * h W_u) W_d

The leading ``first_k_dense_replace`` blocks have one gated MLP of
``intermediate_size``. What the experts held elsewhere would add is left out.

K-FAC layers: every projection whose two factor sides are at most
``kfac.max_factor_side``, less the groups ``kfac.exclude`` names
(``dense_layers``, ``shared_expert``, ``down_banks``); the expert banks are ``bank`` layers (one per
projection, ``[E, a, m]``), handed every held expert's output for every row
and the rows routed (``reference/kfac_sgd.py``).

So that it fits beside a configuration that fills a chip: attention takes the
queries in blocks, and a block of the model none of whose K-FAC layers is on
the tape (``Steps`` with layer groups) is recomputed in the backward pass
(``jax.checkpoint``); neither changes a number."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


class Model:
    rows_independent = True

    def __init__(self, cfg, traffic=None):
        self.cfg = cfg
        self.held = tuple(cfg["held_experts"])  # (first, count)
        exclude = set(cfg["kfac"].get("exclude", ()))  # groups of projections left to SGD
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        self.layers, self.by_block = [], []
        for i in range(cfg["num_hidden_layers"]):
            dense = lambda sub, kind="dense": {
                "name": f"layer_{i}/{sub}", "path": (f"layer_{i}",) + tuple(sub.split("/")),
                "kind": kind, "bias": False}
            is_dense = i < cfg["first_k_dense_replace"]
            side = 0 if is_dense and "dense_layers" in exclude else cfg["kfac"]["max_factor_side"]
            block = []
            if max(d, cfg["q_lora_rank"]) <= side:
                block.append(dense("attn/q_a"))
            if max(d, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) <= side:
                block.append(dense("attn/kv_a"))
            if not is_dense and max(d, f) <= side:
                shared = () if "shared_expert" in exclude else ("shared_gate", "shared_up", "shared_down")
                banks = ("gate", "up") + (() if "down_banks" in exclude else ("down",))
                block += [dense(f"mlp/{s}") for s in ("router",) + shared]
                block += [dense(f"mlp/{s}", "bank") for s in banks]
            elif is_dense and max(d, cfg["intermediate_size"]) <= side:
                block += [dense(f"mlp/{s}") for s in ("gate", "up", "down")]
            self.layers += block
            self.by_block.append({layer["name"] for layer in block})

    # -- pieces ------------------------------------------------------------

    def _norm(self, p, x, prec):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.cfg["rms_norm_eps"])
        return prec.store(y * p["scale"])

    @staticmethod
    def _mm(x, kernel, prec):
        return jnp.matmul(prec.operand(x), prec.operand(kernel), preferred_element_type=jnp.float32)

    def _proj(self, tape, name, p, x, prec):
        """A projection that is a K-FAC layer where its sides allow."""
        y = prec.store(self._mm(x, p["kernel"], prec))
        return tape.layer(name, x, y) if any(name in names for names in self.by_block) else y

    def _rope(self, x):
        """Half-split rotary pairing over the last axis of ``[B, T, H, d]``."""
        half = x.shape[-1] // 2
        freq = self.cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
        cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
        x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    @staticmethod
    def _softmax_attention(q, k, v, prec):
        """Causal attention over ``[B, T, H, d]``, the queries a block at a time."""
        b, t, h, dq = q.shape
        block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

        @jax.checkpoint
        def one(args):
            qb, start = args  # [B, block, H, d]
            s = jnp.einsum("bthd,bshd->bhts", prec.operand(qb), prec.operand(k),
                           preferred_element_type=jnp.float32) / math.sqrt(dq)
            causal = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
            p = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
            return jnp.einsum("bhts,bshd->bthd", prec.operand(p), prec.operand(v),
                              preferred_element_type=jnp.float32)

        blocks = jnp.moveaxis(q.reshape(b, t // block, block, h, dq), 1, 0)
        out = jax.lax.map(one, (blocks, jnp.arange(0, t, block)))
        return prec.store(jnp.moveaxis(out, 0, 1).reshape(b, t, h * v.shape[-1]))

    def _attention(self, tape, name, p, h, prec):
        c = self.cfg
        b, t, _ = h.shape
        nh, nope, rot, vd, lat = (c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                                  c["v_head_dim"], c["kv_lora_rank"])
        c_q = self._norm(p["q_norm"], self._proj(tape, f"{name}/q_a", p["q_a"], h, prec), prec)
        kv = self._proj(tape, f"{name}/kv_a", p["kv_a"], h, prec)
        c_kv = self._norm(p["kv_norm"], kv[..., :lat], prec)
        q = prec.store(self._mm(c_q, p["q_b"]["kernel"], prec)).reshape(b, t, nh, nope + rot)
        kvb = prec.store(self._mm(c_kv, p["kv_b"]["kernel"], prec)).reshape(b, t, nh, nope + vd)
        k_rope = self._rope(kv[..., lat:].reshape(b, t, 1, rot))
        q = jnp.concatenate([q[..., :nope].astype(jnp.float32), self._rope(q[..., nope:])], axis=-1)
        k = jnp.concatenate([kvb[..., :nope].astype(jnp.float32),
                             jnp.broadcast_to(k_rope, (b, t, nh, rot))], axis=-1)
        o = self._softmax_attention(prec.store(q), prec.store(k), kvb[..., nope:], prec)
        return prec.store(self._mm(o, p["o"]["kernel"], prec))

    def _gated(self, tape, name, p, h, prec, prefix=""):
        g = self._proj(tape, f"{name}/{prefix}gate", p[f"{prefix}gate"], h, prec)
        u = self._proj(tape, f"{name}/{prefix}up", p[f"{prefix}up"], h, prec)
        return self._proj(tape, f"{name}/{prefix}down", p[f"{prefix}down"], prec.store(jax.nn.silu(g) * u), prec)

    def _bank(self, tape, name, p, x, routed, prec):
        """``[T, E, m]``: every held expert's output for every row; ``x`` is
        ``[T, a]`` (one input) or ``[T, E, a]`` (each expert's own)."""
        e, a, m = p["kernel"].shape
        if x.ndim == 3:
            y = jnp.swapaxes(jnp.einsum("eta,eam->etm", prec.operand(jnp.swapaxes(x, 0, 1)),
                                        prec.operand(p["kernel"]), preferred_element_type=jnp.float32), 0, 1)
        else:  # one product against the experts' kernels side by side
            y = self._mm(x, jnp.swapaxes(p["kernel"], 0, 1).reshape(a, e * m), prec).reshape(-1, e, m)
        on_tape = any(name in names for names in self.by_block)
        return tape.layer(name, x, prec.store(y), rows=routed) if on_tape else prec.store(y)

    def _experts(self, tape, name, p, h, prec):
        c = self.cfg
        b, t, d = h.shape
        hf = h.reshape(b * t, d)
        # the router in float32 at highest, as the published implementations compute it
        logits = jnp.matmul(hf.astype(jnp.float32), p["router"]["kernel"], precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(tape.layer(f"{name}/router", hf, logits))
        top, chosen = jax.lax.top_k(scores, c["num_experts_per_tok"])
        top = c["routed_scaling_factor"] * top / jnp.sum(top, axis=-1, keepdims=True)
        first, count = self.held
        hit = chosen[:, :, None] == (first + jnp.arange(count))[None, None, :]  # [T, k, E]
        weight = jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)  # [T, E], nought where not routed
        routed = jnp.any(hit, axis=1).astype(jnp.float32)
        act = jax.nn.silu(self._bank(tape, f"{name}/gate", p["gate"], hf, routed, prec)) \
            * self._bank(tape, f"{name}/up", p["up"], hf, routed, prec)
        experts = self._bank(tape, f"{name}/down", p["down"], prec.store(act), routed, prec)
        moe = jnp.sum(weight[:, :, None] * experts, axis=1)
        return prec.store(moe).reshape(b, t, d) + self._gated(tape, name, p, h, prec, prefix="shared_")

    def _block(self, i, p, x, tape, prec):
        name = f"layer_{i}"
        x = x + self._attention(tape, f"{name}/attn", p["attn"], self._norm(p["norm_attn"], x, prec), prec)
        h = self._norm(p["norm_mlp"], x, prec)
        if i < self.cfg["first_k_dense_replace"]:
            return x + self._gated(tape, f"{name}/mlp", p["mlp"], h, prec)
        return x + self._experts(tape, f"{name}/mlp", p["mlp"], h, prec)

    # -- the loss ------------------------------------------------------------

    def loss(self, params, batch, tape, prec):
        tokens, targets = batch
        x = prec.store(params["embed"]["embedding"][tokens])
        for i in range(self.cfg["num_hidden_layers"]):
            on_tape = tape.only is None or bool(self.by_block[i] & set(tape.only))
            block = lambda p, x, i=i: self._block(i, p, x, tape, prec)
            # a block with no layer on the tape records nothing: recompute it
            x = (block if on_tape else jax.checkpoint(block))(params[f"layer_{i}"], x)
        x = self._norm(params["norm_f"], x, prec)
        logits = self._mm(x, params["head"]["kernel"], prec)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0])
