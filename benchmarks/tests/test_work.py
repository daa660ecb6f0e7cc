"""``work/``: operations and bytes from shapes, against hand counts and the
repository's own XLA counts."""

import json
import os

import pytest

import run as bench

HERE = bench.HERE


def cell_work(config, traffic):
    cfg = bench.load_json(HERE, "configs", config + ".json")
    mix = bench.load_json(HERE, "traffic", traffic + ".json")
    w = bench.load_module(HERE, "work", cfg["work"] + ".py").work(cfg, mix, 1)
    w.update(bench.load_module(HERE, "work", "common.py").kfac_work(w["layers"]))
    return cfg, mix, w


def test_resnet50_model_flops_against_xla_count():
    # docs/flops_r4_b128.json: XLA cost_analysis of the CPU-lowered SGD step,
    # 3065.909 GFLOP for 128 images = 23.95 GFLOP/image. Ours counts 3 x the
    # convolutions' and head's forward multiply-adds: 24.54. The difference:
    # XLA leaves out the stem's input gradient (2 x 118 M multiply-adds,
    # 0.24 GFLOP: nothing needs d loss / d image) and adds BatchNorm, ReLU,
    # pooling, the loss and the optimizer (~0.35 GFLOP together): within 3%.
    _, _, w = cell_work("rn50_imagenet", "b128_f10_k100")
    assert w["forward_flops_per_sample"] == pytest.approx(2 * 4.089e9, rel=2e-3)  # 4.09 GMACs
    with open(os.path.join(bench.ROOT, "docs", "flops_r4_b128.json")) as f:
        xla = json.loads(f.readline())
    assert xla["arm"] == "sgd"
    assert w["model_flops_per_sample"] == pytest.approx(xla["gflops"] * 1e9 / 128, rel=0.03)
    assert len(w["layers"]) == 54


def test_resnet50_kfac_work_against_xla_count():
    # the same file's inverse arm: precond - sgd = 156.4 GFLOP a step for the
    # apply, factors - precond = 6501 GFLOP a capture step (with the running
    # averages); ours: 156.2 and 6420
    _, _, w = cell_work("rn50_imagenet", "b128_f10_k100")
    assert w["apply"]["flops"] == pytest.approx((3222.298 - 3065.909) * 1e9, rel=0.01)
    assert w["capture"]["flops"] == pytest.approx((9723.315 - 3222.298) * 1e9, rel=0.02)


def test_hand_count_one_conv_and_one_dense():
    common = bench.load_module(HERE, "work", "common.py")
    # a 3x3 conv 64 -> 64 on 56x56, batch 128: A side 576, G side 64, rows 128*56*56
    conv = {"a_side": 576, "g_side": 64, "rows": 128 * 56 * 56,
            "in_elems": 128 * 56 * 56 * 64, "out_elems": 128 * 56 * 56 * 64}
    cap = common.capture_work([conv])
    assert cap["flops"] == 2 * 401408 * (576 * 576 + 64 * 64)
    assert cap["bytes"] == 4 * (2 * 128 * 56 * 56 * 64 + 2 * (576 * 576 + 64 * 64))
    app = common.apply_work([conv])
    assert app["flops"] == 2 * 64 * 64 * 576 + 2 * 64 * 576 * 576
    # a dense 768 -> 3072 with bias on 8 x 1024 tokens: A side 769, G side 3072
    dense = {"a_side": 769, "g_side": 3072, "rows": 8192,
             "in_elems": 8192 * 768, "out_elems": 8192 * 3072}
    assert common.capture_work([dense])["flops"] == 2 * 8192 * (769**2 + 3072**2)
    assert common.apply_work([dense])["flops"] == 2 * 3072**2 * 769 + 2 * 3072 * 769**2
    peak = bench.load_json(HERE, "peaks.json")["devices"]["TPU v5 lite"]
    seconds, bound = common.least_seconds({"flops": 197e12, "bytes": 1}, peak)
    assert (seconds, bound) == (1.0, "compute")
    seconds, bound = common.least_seconds({"flops": 1, "bytes": 819e9}, peak)
    assert (seconds, bound) == (1.0, "memory")


def test_gpt2_model_flops_are_6n_tokens_plus_attention():
    cfg, mix, w = cell_work("gpt2_124m", "t1024_b8_f1_k10")
    d, v, t, nl = 768, 50257, 1024, 12
    n = nl * 12 * d * d + d * v  # parameters in matrix products, tied head counted once
    assert w["matmul_params"] == n
    attention = 3 * nl * 4 * d * (t + 1) / 2 * t
    assert w["model_flops_per_sample"] == pytest.approx(6 * n * t + attention)
    assert len(w["layers"]) == 48
    # factor sides per block: A 769, 769, 769, 3073 and G 2304, 768, 3072, 768
    block0 = [(l["a_side"], l["g_side"]) for l in w["layers"][:4]]
    assert block0 == [(769, 2304), (769, 768), (769, 3072), (3073, 768)]
    assert w["factor_elements"] == 12 * (3 * 769**2 + 3073**2 + 2304**2 + 2 * 768**2 + 3072**2)


def test_layer_lists_match_what_the_program_discovers():
    # the yardstick's own layer list against capture.discover_layers, at tiny inputs
    import jax.numpy as jnp

    from kfac_pytorch_tpu import capture
    from kfac_pytorch_tpu.models import imagenet_resnet, transformer_lm

    _, _, w = cell_work("rn50_imagenet", "b128_f10_k100")
    model = imagenet_resnet.get_model("resnet50")
    assert len(capture.discover_layers(model, jnp.zeros((1, 32, 32, 3)), train=True)) == len(w["layers"])
    _, _, w = cell_work("gpt2_124m", "t1024_b8_f1_k10")
    lm = transformer_lm.get_model(64, max_len=8, d_model=48, n_heads=12, n_layers=12, tie_embeddings=True)
    assert len(capture.discover_layers(lm, jnp.zeros((1, 8), jnp.int32), train=True)) == len(w["layers"])
