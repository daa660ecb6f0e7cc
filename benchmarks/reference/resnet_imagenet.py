"""Plain ResNet (v1.5 bottleneck, torchvision layout) forward pass and loss
in ``jax.numpy``/``lax``, for the output check. Follows He et al. 2015 with
the stride on the 3x3 convolution; BatchNorm in training mode (batch
statistics, biased variance). Parameter names are those of the tree the
benchmark's weights come in (flax auto-names): that is the only thing it
knows of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

rows_independent = False  # BatchNorm couples the rows of a batch


def _conv_layer(name, path, ksize, stride, pad):
    return {
        "name": name, "path": path, "kind": "conv", "bias": False,
        "kernel_size": (ksize, ksize), "strides": (stride, stride),
        "padding": ((pad, pad), (pad, pad)),
    }


class Model:
    rows_independent = False

    def __init__(self, cfg, traffic):
        self.cfg = cfg
        self.blocks = []  # (name, stride, downsample)
        self.layers = [_conv_layer("KFACConv_0", ("KFACConv_0",), 7, 2, 3)]
        in_planes, exp, i = cfg["base_width"], cfg["bottleneck_expansion"], 0
        for stage, count in enumerate(cfg["stage_sizes"]):
            planes = cfg["base_width"] * 2**stage
            for j in range(count):
                stride = 2 if (stage > 0 and j == 0) else 1
                down = stride != 1 or in_planes != planes * exp
                b = f"Bottleneck_{i}"
                self.blocks.append((b, stride, down))
                self.layers.append(_conv_layer(f"{b}/KFACConv_0", (b, "KFACConv_0"), 1, 1, 0))
                self.layers.append(_conv_layer(f"{b}/KFACConv_1", (b, "KFACConv_1"), 3, stride, 1))
                self.layers.append(_conv_layer(f"{b}/KFACConv_2", (b, "KFACConv_2"), 1, 1, 0))
                if down:
                    self.layers.append(_conv_layer(f"{b}/KFACConv_3", (b, "KFACConv_3"), 1, stride, 0))
                in_planes, i = planes * exp, i + 1
        self.layers.append({"name": "KFACDense_0", "path": ("KFACDense_0",), "kind": "dense", "bias": True})
        self._by_name = {l["name"]: l for l in self.layers}

    def _conv(self, tape, name, p, x, prec):
        l = self._by_name[name]
        y = lax.conv_general_dilated(
            prec.operand(x), prec.operand(p["kernel"]), window_strides=l["strides"],
            padding=l["padding"], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return tape.layer(name, x, y)

    def _bn(self, p, x, prec):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(xf - mean), axis=(0, 1, 2))
        y = (xf - mean) * lax.rsqrt(var + self.cfg["batchnorm_epsilon"])
        return prec.store(y * p["scale"] + p["bias"])

    def loss(self, params, batch, tape, prec):
        images, labels = batch
        x = self._conv(tape, "KFACConv_0", params["KFACConv_0"], images, prec)
        x = jax.nn.relu(self._bn(params["BatchNorm_0"], x, prec))
        x = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)),
        )
        for b, _, down in self.blocks:
            p = params[b]
            y = self._conv(tape, f"{b}/KFACConv_0", p["KFACConv_0"], x, prec)
            y = jax.nn.relu(self._bn(p["BatchNorm_0"], y, prec))
            y = self._conv(tape, f"{b}/KFACConv_1", p["KFACConv_1"], y, prec)
            y = jax.nn.relu(self._bn(p["BatchNorm_1"], y, prec))
            y = self._conv(tape, f"{b}/KFACConv_2", p["KFACConv_2"], y, prec)
            y = self._bn(p["BatchNorm_2"], y, prec)
            if down:
                x = self._conv(tape, f"{b}/KFACConv_3", p["KFACConv_3"], x, prec)
                x = self._bn(p["BatchNorm_3"], x, prec)
            x = jax.nn.relu(y + x)
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        head = params["KFACDense_0"]
        logits = jnp.matmul(
            prec.operand(x), prec.operand(head["kernel"]), preferred_element_type=jnp.float32
        ) + head["bias"]
        logits = tape.layer("KFACDense_0", x, logits)
        return smoothed_cross_entropy(logits, labels, self.cfg["label_smoothing"])


def smoothed_cross_entropy(logits, labels, smoothing):
    """Mean over rows of the cross entropy against the smoothed one-hot."""
    n = logits.shape[-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    target = jax.nn.one_hot(labels, n, dtype=jnp.float32)
    target = (1.0 - smoothing) * target + smoothing / n
    return -jnp.mean(jnp.sum(target * logp, axis=-1))
