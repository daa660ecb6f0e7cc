"""How many tokens route otherwise when the matrix products take one
bfloat16 pass (the TPU's default precision, which the configuration states
for the program) than at ``highest`` (the reference)? The reference model of
a cell, forward only, once with operands as they are and once with every
product's operands rounded to bfloat16 (activations kept in float32), the
routers' logits read off the tape; per expert layer the share of tokens
whose top-k set differs, and of tokens for which a held expert joins or
leaves it. Run by hand; a count, not a device number (PERF.md, section 6).

    JAX_PLATFORMS=cpu python benchmarks/tests/routing_flips.py [cell] [seed]
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "examples"), HERE]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import run as bench  # noqa: E402


class OnePass:
    """Products with bfloat16 operands and float32 sums; nothing else rounded."""
    matmul_precision = "default"
    operand = staticmethod(lambda x: x.astype(jnp.bfloat16))
    store = staticmethod(lambda x: x.astype(jnp.float32))


def main(cell_name="glm47f_ep8_f1_k10", seed=1):
    cell = bench.load_cell(cell_name)
    cfg, mix = cell["cfg"], cell["traffic_mix"]
    kf = bench.load_module(HERE, "reference", "kfac_sgd.py")
    weights = bench.load_module(HERE, "weights.py")
    traffic = bench.load_module(HERE, "traffic.py")
    model = bench.load_module(HERE, "reference", cfg["reference"] + ".py").Model(cfg, mix)
    builder = bench.load_module(HERE, "configs", cfg["builder"] + ".py")
    from kfac_pytorch_tpu.parallel.mesh import data_parallel_mesh

    shapes = jax.eval_shape(builder.build(cfg, mix, data_parallel_mesh(jax.devices()[:1]))["init_state"]).params
    params = jax.jit(lambda s: weights.make_weights(shapes, s, cfg["weights"]))(weights.seed_scalar(int(seed)))
    batch = traffic.make_pool(dict(mix, pool=1), cfg, 1, int(seed))[0]

    def logits(prec):
        tape = kf.Tape()
        with jax.default_matmul_precision("highest" if prec is None else "default"):
            model.loss(params, batch, tape, prec or kf.Precision())
        return {n: np.asarray(v) for n, v in tape.outputs.items() if n.endswith("/router")}

    exact, rounded = logits(None), logits(OnePass)
    k, (first, count) = cfg["num_experts_per_tok"], cfg["held_experts"]
    for name in sorted(exact):
        a = np.sort(np.argsort(-exact[name], axis=-1)[:, :k], axis=-1)
        b = np.sort(np.argsort(-rounded[name], axis=-1)[:, :k], axis=-1)
        held = lambda s: [set(e for e in row if first <= e < first + count) for row in s]
        print(json.dumps({
            "layer": name, "tokens": len(a), "share_routed_otherwise": float(np.mean(np.any(a != b, axis=-1))),
            "share_touching_a_held_expert": float(np.mean([x != y for x, y in zip(held(a), held(b))])),
        }), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
