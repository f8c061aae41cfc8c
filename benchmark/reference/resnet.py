"""Reference bottleneck ResNet (He et al. 2015, arXiv:1512.03385, Table 1) in
float32: stages of ``1x1 -> 3x3 (stride) -> 1x1 x4`` blocks with a projection
shortcut where the shape changes, global average pool, linear classifier.

Departures from the paper, both the program's and both stated in
``configs/resnet50_cifar100.json``: the CIFAR stem (one 3x3 stride-1 convolution,
no max-pool) and GroupNorm with 8 groups where the paper has BatchNorm.

Parameters arrive as the program's tree under flax's automatic names
(``Conv_0``, ``GroupNorm_0``, ``BottleneckBlock_<i>`` holding ``Conv_0..3`` and
``GroupNorm_0..3``, ``Dense_0``). Images are NHWC, kernels HWIO.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def conv(x, kernel, stride=1):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def group_norm(x, p, groups, eps):
    b, h, w, c = x.shape
    g = x.reshape(b, h * w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean((g - mean) ** 2, axis=(1, 3), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + eps)
    return g.reshape(b, h, w, c) * p["scale"] + p["bias"]


def bottleneck(x, p, stride, cfg):
    def norm(y, i):
        return group_norm(y, p[f"GroupNorm_{i}"], cfg["norm"]["groups"], cfg["norm"]["eps"])

    y = jax.nn.relu(norm(conv(x, p["Conv_0"]["kernel"]), 0))
    y = jax.nn.relu(norm(conv(y, p["Conv_1"]["kernel"], stride), 1))
    y = norm(conv(y, p["Conv_2"]["kernel"]), 2)
    if "Conv_3" in p:
        x = norm(conv(x, p["Conv_3"]["kernel"], stride), 3)
    return jax.nn.relu(y + x)


def logits(params: dict, images, cfg: dict):
    """[B, H, W, C] float images -> [B, classes] float32 logits."""
    x = conv(images, params["Conv_0"]["kernel"])
    x = jax.nn.relu(group_norm(x, params["GroupNorm_0"], cfg["norm"]["groups"], cfg["norm"]["eps"]))
    index = 0
    for stage, n_blocks in enumerate(cfg["stage_sizes"]):
        for j in range(n_blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            x = bottleneck(x, params[f"BottleneckBlock_{index}"], stride, cfg)
            index += 1
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]


def loss(params: dict, images, labels, cfg: dict):
    """Mean cross-entropy over the batch."""
    logp = jax.nn.log_softmax(logits(params, images, cfg), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
