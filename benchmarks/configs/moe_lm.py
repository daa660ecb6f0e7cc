"""Builder for sparse-expert LM configurations (``glm4_moe_lite``): the LM
trainer's own ``build()`` (``examples/train_transformer_lm.py``), called with
what its ``main`` passes for ``--model glm_moe_lite``: the model of
``models/glm_moe_lite.py`` cut to this rank's share, the discovered K-FAC
layers with their shared inputs, ``make_sgd`` and ``make_train_step``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from train_transformer_lm import build as lm_build  # a program without it cannot run this configuration


def _attention(cfg):
    if cfg["attention"] == "flash":
        # what best_attention_fn() returns on a single TPU device
        from kfac_pytorch_tpu.ops.flash_attention import flash_attention

        return functools.partial(flash_attention, interpret=False)
    from kfac_pytorch_tpu.parallel.context import full_attention

    return full_attention


def model_sizes(cfg):
    """The fields of ``GLMMoELiteConfig`` from a configuration's file."""
    first, count = cfg["held_experts"]
    if count != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here: held_experts = (first, count)")
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps")
    return dict(
        {k: cfg[k] for k in keys}, first_k_dense=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["published"]["n_routed_experts"], rope_theta=float(cfg["rope_theta"]),
        held=(first, count), kfac_max_side=cfg["kfac"]["max_factor_side"],
        kfac_exclude=tuple(cfg["kfac"].get("exclude", ())))


def build(cfg, traffic, mesh, kfac_on=True, lower_precision=False):
    if lower_precision:
        raise ValueError(
            "models/glm_moe_lite.py has no lower-precision path of its own; "
            "the control is the reference in bfloat16 (reference/glm_moe_lite.py)"
        )
    world = mesh.devices.size
    n, t = traffic["per_chip_batch"] * world, traffic["seq_len"]
    k = cfg["kfac"]
    built = lm_build(
        "glm_moe_lite", model_sizes(cfg), global_batch=n, seq_len=t, attention_fn=_attention(cfg),
        momentum=cfg["momentum"], weight_decay=cfg["weight_decay"], grad_clip=cfg["grad_clip"],
        remat=cfg["remat"],
        kfac_kwargs=dict(
            factor_decay=k["stat_decay"], damping=k["damping"], kl_clip=k["kl_clip"],
            fac_update_freq=traffic["fac_update_freq"], kfac_update_freq=traffic["kfac_update_freq"],
            mesh=mesh if world > 1 else None, precond_method=k["precond_method"],
        ) if kfac_on else None,
    )
    return {
        "kfac": built["kfac"],
        "init_state": lambda: built["init_state"](0),
        "train_step": built["train_step"],
        "batch_struct": (
            jax.ShapeDtypeStruct((n, t), jnp.int32),
            jax.ShapeDtypeStruct((n, t), jnp.int32),
        ),
        "epoch": None,
    }
