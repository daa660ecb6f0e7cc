"""Work of a ``glm4_moe_lite`` training step on one expert-parallel rank,
from the configuration's sizes. Model FLOPs per sequence are 6 x (the
parameters of the matrices a token really passes) x tokens: the latent
attention's five projections, the dense layers' MLP, and per expert layer the
router, the shared expert and the held experts at their mean load
(``num_experts_per_tok`` x held / published experts of an expert a token),
the head over the vocabulary slice; plus causal attention at
``qk_head_dim + v_head_dim`` a head ((T+1)/2 keys on average), forward once
and backward twice. Look-ups, norms, rotary positions, the routing and the
loss are left out.

``experts`` and ``attention`` are the least work of the grouped products and
of the attention kernel in one step (forward and backward; a recomputed
forward pass does not count), for their rooflines."""

from __future__ import annotations

F32 = 4


def work(cfg, traffic, chips):
    n, t = traffic["per_chip_batch"] * chips, traffic["seq_len"]
    d, f, ff = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["intermediate_size"]
    nh, nope, rot, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                         cfg["v_head_dim"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nl, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    ne = nl - nd
    held, routed, k = cfg["n_routed_experts"], cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"]
    load = k * held / routed  # held experts a token passes, on average
    attn_params = d * ql + ql * nh * (nope + rot) + d * (kvl + rot) + kvl * nh * (nope + vd) + nh * vd * d
    expert = 3 * d * f
    matmul_params = (nl * attn_params + nd * 3 * d * ff + ne * (d * routed + expert + load * expert)
                     + d * cfg["vocab_size"])
    attention = nl * 2 * nh * (nope + rot + vd) * (t + 1) / 2  # QK^T and PV, per token, causal
    fwd = t * (2 * matmul_params + attention)

    side = cfg["kfac"]["max_factor_side"]
    exclude = set(cfg["kfac"].get("exclude", ()))  # groups of projections left to SGD
    rows = n * t
    layers = []

    def dense(name, a, g, rows=rows, group=None):
        if max(a, g) <= side and group not in exclude:
            layers.append({"name": name, "a_side": a, "g_side": g, "rows": rows,
                           "in_elems": rows * a, "out_elems": rows * g})

    for i in range(nl):
        of_dense = "dense_layers" if i < nd else None
        dense(f"layer{i}.q_a", d, ql, group=of_dense)
        dense(f"layer{i}.kv_a", d, kvl + rot, group=of_dense)
        if i < nd:
            for name, a, g in (("gate", d, ff), ("up", d, ff), ("down", ff, d)):
                dense(f"layer{i}.{name}", a, g, group=of_dense)
            continue
        dense(f"layer{i}.router", d, routed)
        for name, a, g in (("gate", d, f), ("up", d, f), ("down", f, d)):
            dense(f"layer{i}.shared_{name}", a, g, group="shared_expert")
            for e in range(held):  # one entry per expert, at its mean load
                dense(f"layer{i}.{name}.{e}", a, g, rows=rows * k // routed,
                      group="down_banks" if name == "down" else None)

    held_rows = n * t * load  # token-expert pairs on held experts, a layer
    experts = {
        # forward, input gradient and kernel gradient of the three grouped products
        "flops": ne * 3 * 2 * held_rows * expert,
        # the kernels read twice and their gradients written once; the rows' activations read and written
        "bytes": ne * F32 * (3 * held * expert + 3 * 2 * held_rows * (d + 2 * f + d)),
    }
    pairs = n * nh * t * (t + 1) / 2
    attention_work = {
        # QK^T and PV forward; dV, dP, dQ, dK and the recomputed QK^T backward: 7 products of 2 x pairs x head size
        "flops": nl * 2 * pairs * (2 * (nope + rot) + 2 * vd + 3 * (nope + rot)),
        # q, k, v, o read and their four gradients written, the output gradient read
        "bytes": nl * F32 * n * t * nh * (5 * (nope + rot) + 4 * vd),
    }
    return {
        "model_flops_per_sample": 3 * fwd,
        "forward_flops_per_sample": fwd,
        "matmul_params": matmul_params,
        "layers": layers,
        "experts": experts,
        "attention": attention_work,
    }
