"""Builder for decoder-only LM configurations. ``examples/
train_transformer_lm.py`` has no ``build()`` the benchmark can hold (PERF.md,
section 7, "left for PRs of their own"), so this assembles the same with the arguments its
``main`` passes at :439-:600: ``models/transformer_lm.get_model``,
``capture.discover_layers``, ``KFAC(...)``, ``make_sgd`` and
``make_train_step``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _attention(cfg):
    if cfg["attention"] == "flash":
        # what best_attention_fn() returns on a single TPU device
        from kfac_pytorch_tpu.ops.flash_attention import flash_attention

        return functools.partial(flash_attention, interpret=False)
    from kfac_pytorch_tpu.parallel.context import full_attention

    return full_attention


def build(cfg, traffic, mesh, kfac_on=True, lower_precision=False):
    if lower_precision:
        raise ValueError(
            "models/transformer_lm.py has no lower-precision path of its own; "
            "the control is the reference in bfloat16 (reference/transformer_lm.py)"
        )
    from kfac_pytorch_tpu import KFAC, capture
    from kfac_pytorch_tpu.models import transformer_lm
    from kfac_pytorch_tpu.training import TrainState, make_train_step
    from kfac_pytorch_tpu.training.step import make_sgd

    world = mesh.devices.size
    n, t = traffic["per_chip_batch"] * world, traffic["seq_len"]
    model = transformer_lm.get_model(
        cfg["vocab_size"], max_len=cfg["n_positions"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
        attention_fn=_attention(cfg), kfac_embedding=cfg["kfac_embedding"],
        qkv_lens=False, tie_embeddings=cfg["tie_word_embeddings"], remat=False,
    )
    init_toks = jnp.zeros((n, t), jnp.int32)
    tx = make_sgd(momentum=cfg["momentum"], weight_decay=cfg["weight_decay"])
    kfac = None
    if kfac_on:
        k = cfg["kfac"]
        kfac = KFAC(
            layers=capture.discover_layers(model, init_toks, train=True),
            factor_decay=k["stat_decay"],
            damping=k["damping"],
            kl_clip=k["kl_clip"],
            fac_update_freq=traffic["fac_update_freq"],
            kfac_update_freq=traffic["kfac_update_freq"],
            mesh=mesh if world > 1 else None,
            precond_method=k["precond_method"],
        )

    def init_state():
        params = model.init(jax.random.PRNGKey(0), init_toks, train=True)["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats={},
            opt_state=tx.init(params),
            kfac_state=kfac.init(params) if kfac else None,
        )

    train_step = make_train_step(
        model, tx, kfac, train_kwargs={"train": True},
        grad_clip=cfg["grad_clip"],
        sgd_hyper=(cfg["momentum"], cfg["weight_decay"]) if kfac else None,
    )
    return {
        "kfac": kfac,
        "init_state": init_state,
        "train_step": train_step,
        "batch_struct": (
            jax.ShapeDtypeStruct((n, t), jnp.int32),
            jax.ShapeDtypeStruct((n, t), jnp.int32),
        ),
        "epoch": None,
    }
