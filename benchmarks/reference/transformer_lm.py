"""Plain decoder-only transformer LM (GPT-2's block: pre-LayerNorm, learned
positions, fused QKV, tanh-GELU MLP, tied output head) forward pass and loss
in ``jax.numpy``, for the output check. Follows Radford et al. 2019. Parameter
names are those of the tree the benchmark's weights come in."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


class Model:
    rows_independent = True

    def __init__(self, cfg, traffic):
        self.cfg = cfg
        self.layers = []
        for i in range(cfg["n_layer"]):
            for sub in ("qkv", "out", "ff1", "ff2"):
                self.layers.append({
                    "name": f"block_{i}/{sub}", "path": (f"block_{i}", sub),
                    "kind": "dense", "bias": True,
                })

    def _dense(self, tape, name, p, x, prec):
        y = jnp.matmul(
            prec.operand(x), prec.operand(p["kernel"]), preferred_element_type=jnp.float32
        ) + p["bias"]
        return tape.layer(name, x, prec.store(y))

    def _ln(self, p, x, prec):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.cfg["layer_norm_epsilon"])
        return prec.store(y * p["scale"] + p["bias"])

    def _attention(self, qkv, prec):
        b, t, _ = qkv.shape
        h = self.cfg["n_head"]
        hd = self.cfg["n_embd"] // h
        q, k, v = (a.reshape(b, t, h, hd) for a in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bthd,bshd->bhts", prec.operand(q), prec.operand(k),
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(causal[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", prec.operand(p), prec.operand(v),
                       preferred_element_type=jnp.float32)
        return prec.store(o.reshape(b, t, h * hd))

    def loss(self, params, batch, tape, prec):
        tokens, targets = batch
        t = tokens.shape[1]
        table = params["tok_embed"]["embedding"]
        x = prec.store(table[tokens] + params["pos_embed"]["embedding"][None, :t])
        for i in range(self.cfg["n_layer"]):
            p = params[f"block_{i}"]
            h = self._ln(p["ln_attn"], x, prec)
            a = self._attention(self._dense(tape, f"block_{i}/qkv", p["qkv"], h, prec), prec)
            x = x + self._dense(tape, f"block_{i}/out", p["out"], a, prec)
            h = self._ln(p["ln_mlp"], x, prec)
            f = jax.nn.gelu(self._dense(tape, f"block_{i}/ff1", p["ff1"], h, prec), approximate=True)
            x = x + self._dense(tape, f"block_{i}/ff2", p["ff2"], f, prec)
        x = self._ln(params["ln_f"], x, prec)
        logits = jnp.matmul(prec.operand(x), prec.operand(table.T), preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(picked)
