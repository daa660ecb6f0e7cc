"""Work of an ImageNet ResNet (bottleneck v1.5) step, from the
configuration's sizes. Model FLOPs are the convolutions' and the head's
multiply-adds, forward once and backward twice (input and weight gradients),
as model-FLOP utilisation is conventionally counted; BatchNorm, ReLU, pooling
and the loss are left out (under 1%)."""

from __future__ import annotations


def conv_layers(cfg):
    """``[(name, k, cin, cout, stride, in_hw, out_hw)]`` in model order."""
    hw = cfg["image_size"]
    out = [("stem", 7, cfg["image_channels"], cfg["base_width"], 2, hw, hw // 2)]
    hw = hw // 4  # stride-2 stem, stride-2 max pool
    in_planes, exp = cfg["base_width"], cfg["bottleneck_expansion"]
    i = 0
    for stage, count in enumerate(cfg["stage_sizes"]):
        planes = cfg["base_width"] * 2**stage
        for j in range(count):
            stride = 2 if (stage > 0 and j == 0) else 1
            b = f"block{i}"
            out.append((f"{b}.conv1", 1, in_planes, planes, 1, hw, hw))
            out.append((f"{b}.conv2", 3, planes, planes, stride, hw, hw // stride))
            out.append((f"{b}.conv3", 1, planes, planes * exp, 1, hw // stride, hw // stride))
            if stride != 1 or in_planes != planes * exp:
                out.append((f"{b}.down", 1, in_planes, planes * exp, stride, hw, hw // stride))
            hw //= stride
            in_planes = planes * exp
            i += 1
    return out, in_planes


def work(cfg, traffic, chips):
    n = traffic["per_chip_batch"] * chips
    convs, features = conv_layers(cfg)
    fwd = sum(2 * k * k * cin * cout * ohw * ohw for _, k, cin, cout, _, _, ohw in convs)
    fwd += 2 * features * cfg["num_classes"]
    layers = [
        {"name": name, "a_side": k * k * cin, "g_side": cout, "rows": n * ohw * ohw,
         "in_elems": n * ihw * ihw * cin, "out_elems": n * ohw * ohw * cout}
        for name, k, cin, cout, _, ihw, ohw in convs
    ]
    layers.append({"name": "head", "a_side": features + 1, "g_side": cfg["num_classes"],
                   "rows": n, "in_elems": n * features, "out_elems": n * cfg["num_classes"]})
    return {
        "model_flops_per_sample": 3 * fwd,
        "forward_flops_per_sample": fwd,
        "layers": layers,
    }
