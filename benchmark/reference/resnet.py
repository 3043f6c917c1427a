"""Plain reference of ``models/resnet.py``'s bottleneck ResNet in training
mode: float32 ``jax.numpy`` and ``lax.conv_general_dilated``, no flax.
torchvision's v1.5 layout (the stride on the 3x3), NHWC; BatchNorm
normalises with the batch's own biased statistics (epsilon 1e-5); the
loss is cross-entropy against the labels, which is what the program's
KL-divergence against one-hot targets equals.  One departure from
torchvision is the program's and is kept: a strided 3x3 convolution pads
as XLA's "SAME" does (0 before, 1 after on an even input), not 1 and 1.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _same(a):
    return a


def _conv(x, kernel, stride=1, pad=0, operand=_same):
    padding = pad if isinstance(pad, str) else [(pad, pad), (pad, pad)]
    return lax.conv_general_dilated(
        operand(x), operand(kernel), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, eps=1e-5):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride, operand):
    conv = lambda a, name, *how: _conv(a, p[name]["kernel"], *how,
                                       operand=operand)
    y = jax.nn.relu(_batch_norm(conv(x, "Conv_0"), p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(conv(y, "Conv_1", stride, "SAME"),
                                p["BatchNorm_1"]))
    y = _batch_norm(conv(y, "Conv_2"), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(conv(x, "conv_proj", stride), p["norm_proj"])
    return jax.nn.relu(x + y)


def resnet_logits(params, images, stage_sizes=(3, 4, 6, 3), operand=_same):
    """``[B, H, W, C]`` images to ``[B, classes]`` float32 logits, batch
    statistics in every BatchNorm.  ``operand`` is applied to both operands
    of every convolution and of the classifier's product: the identity for
    the reference, a rounding to a lower precision for its control
    (``compare.rounded_to``)."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = _conv(jnp.asarray(images, jnp.float32),
              params["conv_init"]["kernel"], 2, 3, operand)
    x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    block = 0
    for stage, count in enumerate(stage_sizes):
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            x = _bottleneck(x, params[f"Bottleneck_{block}"], stride,
                            operand)
            block += 1
    x = x.mean((1, 2))
    return operand(x) @ operand(params["fc"]["kernel"]) \
        + params["fc"]["bias"]


def classification_loss(logits, labels):
    """Mean cross-entropy against integer labels, nats."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], -1).mean()
