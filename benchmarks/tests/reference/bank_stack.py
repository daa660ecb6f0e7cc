"""Rehearsal reference of a two-kind stack, for the tests and the rehearsal
of ``reference/kfac_sgd.py``'s ``bank`` kind, layer groups and bounded
stacks; no configuration of ``BENCHMARK.json`` runs it and the program has no
such model. Per layer, pre-normalized and residual: one dense projection
d -> d; then a sigmoid router over ``n_routed_experts`` of which every row
takes its ``num_experts_per_tok`` best, their scores normalized and scaled,
and of which the first ``experts_held`` are held here (the others' rows go
elsewhere and add nothing); a gated SiLU expert bank (``gate``, ``up``:
``[E, d, f]``, ``down``: ``[E, f, d]``, fed by each expert's own
activation); and one shared gated SiLU MLP of the same widths. ``n_layer``
such layers between an embedding and a head over the vocabulary.

``Model(cfg, as_dense=True)`` writes every bank as E dense layers whose
unrouted rows are zero, through the engine's ``dense`` kind: what the
``bank`` kind is tested against."""

from __future__ import annotations

import jax
import jax.numpy as jnp

BANKS = (("gate", "hidden_size", "moe_intermediate_size"), ("up", "hidden_size", "moe_intermediate_size"),
         ("down", "moe_intermediate_size", "hidden_size"))


class Model:
    rows_independent = True

    def __init__(self, cfg, traffic=None, as_dense=False):
        self.cfg, self.as_dense = cfg, as_dense
        self.layers = []
        dense = lambda i, sub: {"name": f"layer_{i}/{sub}", "path": (f"layer_{i}", sub), "kind": "dense", "bias": False}
        for i in range(cfg["n_layer"]):
            self.layers += [dense(i, "proj"), dense(i, "router")]
            for sub, _, _ in BANKS:
                if as_dense:
                    self.layers += [dense(i, f"{sub}_{e}") for e in range(cfg["experts_held"])]
                else:
                    self.layers.append({**dense(i, sub), "kind": "bank"})
            self.layers += [dense(i, f"shared_{sub}") for sub, _, _ in BANKS]

    def param_shapes(self):
        c = self.cfg
        leaf = lambda *shape: {"kernel": jax.ShapeDtypeStruct(shape, jnp.float32)}
        tree = {"embed": {"embedding": jax.ShapeDtypeStruct((c["vocab_size"], c["hidden_size"]), jnp.float32)},
                "head": leaf(c["hidden_size"], c["vocab_size"])}
        for i in range(c["n_layer"]):
            block = {"proj": leaf(c["hidden_size"], c["hidden_size"]),
                     "router": leaf(c["hidden_size"], c["n_routed_experts"])}
            for sub, a, m in BANKS:
                block[sub] = leaf(c["experts_held"], c[a], c[m])
                block[f"shared_{sub}"] = leaf(c[a], c[m])
            tree[f"layer_{i}"] = block
        return tree

    def split_banks(self, params):
        """The same weights for the ``as_dense`` model: each bank's kernel as E leaves."""
        out = {}
        for key, block in params.items():
            out[key] = dict(block)
            for sub, _, _ in BANKS:
                if key.startswith("layer_"):
                    kernel = out[key].pop(sub)["kernel"]
                    out[key].update({f"{sub}_{e}": {"kernel": kernel[e]} for e in range(kernel.shape[0])})
        return out

    def _dense(self, tape, name, p, x, prec):
        y = jnp.matmul(prec.operand(x), prec.operand(p["kernel"]), preferred_element_type=jnp.float32)
        return tape.layer(name, x, prec.store(y))

    def _bank(self, tape, i, sub, block, x, routed, prec):
        """``[T, E, m]``: every held expert's output for every row; ``x`` is
        ``[T, a]`` (one input) or ``[T, E, a]`` (each expert's own)."""
        own = x.ndim == 3
        if not self.as_dense:
            kernel = prec.operand(block[sub]["kernel"])
            y = jnp.einsum("tea,eam->tem" if own else "ta,eam->tem", prec.operand(x), kernel,
                           preferred_element_type=jnp.float32)
            return tape.layer(f"layer_{i}/{sub}", x, prec.store(y), rows=routed)
        outs = []
        for e in range(routed.shape[1]):
            xe = (x[:, e] if own else x) * routed[:, e:e + 1]
            outs.append(self._dense(tape, f"layer_{i}/{sub}_{e}", block[f"{sub}_{e}"], xe, prec))
        return jnp.stack(outs, axis=1)

    @staticmethod
    def _norm(x, prec):
        xf = x.astype(jnp.float32)
        return prec.store(xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + 1e-6))

    def loss(self, params, batch, tape, prec):
        c = self.cfg
        tokens, targets = (a.reshape(-1) for a in batch)
        x = prec.store(params["embed"]["embedding"][tokens])
        for i in range(c["n_layer"]):
            block = params[f"layer_{i}"]
            x = x + self._dense(tape, f"layer_{i}/proj", block["proj"], self._norm(x, prec), prec)
            h = self._norm(x, prec)
            scores = jax.nn.sigmoid(self._dense(tape, f"layer_{i}/router", block["router"], h, prec))
            top, chosen = jax.lax.top_k(scores, c["num_experts_per_tok"])
            top = c["routed_scaling_factor"] * top / jnp.sum(top, axis=-1, keepdims=True)
            held = jnp.arange(c["experts_held"])
            hit = chosen[:, :, None] == held[None, None, :]  # [T, k, E]
            weight = jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)  # [T, E], nought where not routed
            routed = jnp.any(hit, axis=1).astype(jnp.float32)
            act = jax.nn.silu(self._bank(tape, i, "gate", block, h, routed, prec)) \
                * self._bank(tape, i, "up", block, h, routed, prec)
            experts = self._bank(tape, i, "down", block, prec.store(act), routed, prec)
            moe = jnp.sum(weight[:, :, None] * experts, axis=1)
            shared = jax.nn.silu(self._dense(tape, f"layer_{i}/shared_gate", block["shared_gate"], h, prec)) \
                * self._dense(tape, f"layer_{i}/shared_up", block["shared_up"], h, prec)
            x = x + moe + self._dense(tape, f"layer_{i}/shared_down", block["shared_down"], prec.store(shared), prec)
        logits = jnp.matmul(prec.operand(self._norm(x, prec)), prec.operand(params["head"]["kernel"]),
                            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0])
