"""ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1, 50-layer) as plain
float32 jax.numpy, written from the paper and the Gluon model zoo's layer
list.  The yardstick's own: it imports nothing from mxnet_tpu and takes
the parameters by name (the zoo's names with the net's prefix removed).

v1 as the Gluon zoo builds it: the stride of a downsampling bottleneck
sits on its FIRST 1x1 convolution; that 1x1 and the last 1x1 carry a
bias (the zoo's quirk), the 3x3 and the shortcut projection do not.
Convolution weights are (out, in, kh, kw), activations NHWC.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


def _bn(x, p, name, train, eps):
    if train:       # batch statistics, biased variance (Ioffe & Szegedy)
        mean, var = x.mean((0, 1, 2)), x.var((0, 1, 2))
    else:
        mean, var = p[name + "_running_mean"], p[name + "_running_var"]
    return ((x - mean) * lax.rsqrt(var + eps) * p[name + "_gamma"]
            + p[name + "_beta"])


def logits(params, images, config, train=False):
    """(N, classes) float32 logits for NHWC `images`; `train` selects
    batch statistics in every BatchNorm (there is no dropout)."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps = config["batchnorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(images, jnp.float32)
        x = _conv(x, p["conv2d0_weight"], 2, 3)
        x = jax.nn.relu(_bn(x, p, "batchnorm0", train, eps))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
        for s, blocks in enumerate(config["layers"], start=1):
            i = 0                       # conv/bn counter inside the stage
            for b in range(blocks):
                stride = 2 if (b == 0 and s > 1) else 1
                pre = f"stage{s}_"

                def unit(x, k, stride, pad, bias, i=i, pre=pre):
                    y = _conv(x, p[f"{pre}conv2d{i + k}_weight"], stride, pad)
                    if bias:
                        y = y + p[f"{pre}conv2d{i + k}_bias"]
                    return _bn(y, p, f"{pre}batchnorm{i + k}", train, eps)

                y = jax.nn.relu(unit(x, 0, stride, 0, True))
                y = jax.nn.relu(unit(y, 1, 1, 1, False))
                y = unit(y, 2, 1, 0, True)
                if b == 0:              # projection shortcut
                    x = unit(x, 3, stride, 0, False)
                    i += 4
                else:
                    i += 3
                x = jax.nn.relu(y + x)
        x = x.mean((1, 2))
        return x @ p["dense0_weight"].T + p["dense0_bias"]


def loss(params, images, labels, config, train=True):
    """Mean softmax cross-entropy, BatchNorm on batch statistics."""
    lsm = jax.nn.log_softmax(logits(params, images, config, train), -1)
    return -jnp.take_along_axis(
        lsm, jnp.asarray(labels, jnp.int32)[:, None], -1).mean()
