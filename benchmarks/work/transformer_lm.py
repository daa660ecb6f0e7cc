"""Work of a decoder-only transformer LM step, from the configuration's
sizes. Model FLOPs per sequence are 6 x (parameters in matrix products) x
tokens plus causal attention (each position attends to itself and what is
before it: (T+1)/2 keys on average), forward once and backward twice. The
token and position look-ups, LayerNorm, GELU and the loss are left out."""

from __future__ import annotations


def work(cfg, traffic, chips):
    n, t = traffic["per_chip_batch"] * chips, traffic["seq_len"]
    d, ff, nl, v = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"], cfg["vocab_size"]
    matmul_params = nl * (3 * d * d + d * d + 2 * d * ff) + d * v  # blocks + tied head
    attention = nl * 4 * d * (t + 1) / 2  # QK^T and PV, per token, causal
    fwd = t * (2 * matmul_params + attention)
    layers = []
    for i in range(nl):
        for name, a, g in (("qkv", d, 3 * d), ("out", d, d), ("ff1", d, ff), ("ff2", ff, d)):
            layers.append({"name": f"block{i}.{name}", "a_side": a + 1, "g_side": g,
                           "rows": n * t, "in_elems": n * t * a, "out_elems": n * t * g})
    return {
        "model_flops_per_sample": 3 * fwd,
        "forward_flops_per_sample": fwd,
        "matmul_params": matmul_params,
        "layers": layers,
    }
