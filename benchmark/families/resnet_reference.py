"""Plain reference of the ``resnet`` family: forward pass in training
mode and softmax cross-entropy, float32, precision "highest", no
kernel and no one-pass statistics.

Written from He et al. 2015 (table 1, figure 5 right) with the stride
on the 3x3 convolution of a bottleneck (torchvision's "v1.5"), and
Ioffe & Szegedy 2015 for batch-norm over the batch's own statistics:

    y = gamma (x - mean_B(x)) / sqrt(var_B(x) + eps) + beta
    block(x) = relu(shortcut(x) + bn3(conv3(relu(bn2(conv2(relu(bn1(conv1 x))))))))
    logits = mean_hw(h) W + b ;  loss = mean_n -log softmax(logits_n)[label_n]

Departure, the program's own: TensorFlow-style SAME padding on the
strided convolutions and the max-pool. Batch-norm couples the rows of a
batch, so the whole batch goes through at once (forward only: the
largest float32 activation of ResNet-50 at batch 256 is 0.8 GB)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, w, stride):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _bn(x, p, eps):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _forward_loss(params, images, labels, *, stage_sizes, bottleneck,
                  small_images, eps):
    x = images.astype(jnp.float32)
    y = _conv(x, params["stem_conv"], 1 if small_images else 2)
    y = jax.nn.relu(_bn(y, params["stem_bn"], eps))
    if not small_images:
        y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for s, n_blocks in enumerate(stage_sizes):
        for b in range(n_blocks):
            p = params[f"s{s}b{b}"]
            stride = 2 if (b == 0 and s > 0) else 1
            shortcut = y
            if "proj" in p:
                shortcut = _bn(_conv(y, p["proj"], stride), p["proj_bn"],
                               eps)
            if bottleneck:
                z = jax.nn.relu(_bn(_conv(y, p["conv1"], 1), p["bn1"], eps))
                z = jax.nn.relu(_bn(_conv(z, p["conv2"], stride), p["bn2"],
                                    eps))
                z = _bn(_conv(z, p["conv3"], 1), p["bn3"], eps)
            else:
                z = jax.nn.relu(_bn(_conv(y, p["conv1"], stride), p["bn1"],
                                    eps))
                z = _bn(_conv(z, p["conv2"], 1), p["bn2"], eps)
            y = jax.nn.relu(shortcut + z)
    logits = y.mean((1, 2)) @ params["fc_w"] + params["fc_b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def loss(init, batch, model: dict) -> float:
    """`init` is what the family's model_init returns: (params, the
    running statistics). Training mode does not read the latter."""
    params, _ = init
    images, labels = batch
    fn = jax.jit(_forward_loss, static_argnames=(
        "stage_sizes", "bottleneck", "small_images", "eps"))
    with jax.default_matmul_precision("highest"):
        return float(fn(params, images, labels,
                        stage_sizes=tuple(model["stage_sizes"]),
                        bottleneck=model["bottleneck"],
                        small_images=model["small_images"],
                        eps=model["bn_epsilon"]))
