"""The rehearsal reference alone on the chip: the three checked steps of
``reference/kfac_sgd.py::Steps`` over the stack of ``reference/bank_stack.py``
at the sizes of ``configs/bank_stack.json`` (an expert-bank LM's widths at
hidden 2048, one sequence of 4096 tokens), with the layers in the groups the
file gives, capture every step and a refresh at step 0. It shows that the
plain float32 reference of such a configuration fits beside nothing else:
the allocator's peak and the wall time, one JSON line. Run by hand through
the chip tool, twice in one call: the second process finds its programs in
the compile cache and gives the warm time.

    python benchmarks/tests/rehearse_reference.py [--config bank_stack] [--seed 1] [--groups N] [--allow-cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
TESTS = os.path.dirname(os.path.abspath(__file__))
HERE = os.path.dirname(TESTS)
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import run as bench  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="bank_stack")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--groups", type=int, default=None)
    p.add_argument("--allow-cpu", action="store_true", help="rehearsal only: no device number comes of it")
    a = p.parse_args(argv)
    t_start = time.time()
    bench.place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0] if a.allow_cpu else bench.find_devices(1)[0][0]
    cfg = bench.load_json(TESTS, "configs", a.config + ".json")
    kf = bench.load_module(HERE, "reference", "kfac_sgd.py")
    weights = bench.load_module(HERE, "weights.py")
    model = bench.load_module(TESTS, "reference", cfg["reference"] + ".py").Model(cfg)
    hyper = kf.hyper_of(cfg)
    groups = a.groups or cfg.get("reference_layer_groups", 1)
    shapes = model.param_shapes()
    params = jax.jit(lambda s: weights.make_weights(shapes, s, cfg["weights"]))(weights.seed_scalar(a.seed))
    ids = np.random.default_rng(a.seed).integers(
        0, cfg["vocab_size"], size=(bench.CHECKED_STEPS, cfg["per_chip_batch"], cfg["seq_len"] + 1), dtype=np.int32)
    steps = kf.Steps(model, hyper, groups=groups)
    t0 = time.time()
    state, losses, seconds, resid = steps.init(params), [], [], None
    for k, b in enumerate(ids):
        t = time.time()
        state, loss, grads, r = steps.step(state, (b[:, :-1], b[:, 1:]), jnp.float32(cfg["base_lr"]),
                                           capture=True, refresh=k == 0)
        losses.append(float(loss))
        gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))))
        del grads
        resid = float(r) if r is not None else resid
        seconds.append(time.time() - t)
    jax.block_until_ready(state.params)
    stats = device.memory_stats() or {}
    elements = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    print(json.dumps({
        "config": a.config, "seed": a.seed, "groups": len(steps.groups), "stack_bytes": kf.STACK_BYTES,
        "device": {"platform": device.platform, "kind": device.device_kind},
        "parameters": elements(params), "factor_elements": elements(state.factors),
        "three_steps_s": time.time() - t0, "step_s": seconds, "process_s": time.time() - t_start,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"), "bytes_limit": stats.get("bytes_limit"),
        "losses": losses, "last_grad_norm": gnorm, "inverse_residual": resid,
    }), flush=True)


if __name__ == "__main__":
    main()
