"""Family ``resnet``: a residual network as ``ray_tpu.models.resnet``
builds it (NHWC, bf16 convolutions, batch-norm as a (params, state)
pair), trained on softmax cross-entropy over one repeated synthetic
batch made on the device from the seed.

Workload keys: ``batch`` (images a step) and ``hw`` (image side)."""

from __future__ import annotations

from benchmark.common import Pieces, key_seed, make_optimizer


def _model_cfg(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models import resnet

    if (model["bn_momentum"], model["bn_epsilon"]) != (0.9, 1e-5):
        raise ValueError("the program's batch-norm has momentum 0.9 and "
                         "eps 1e-5 built in")
    return resnet.ResNetConfig(
        stage_sizes=tuple(model["stage_sizes"]),
        bottleneck=model["bottleneck"], num_classes=model["num_classes"],
        width=model["width"], dtype=getattr(jnp, model["compute_dtype"]),
        small_images=model["small_images"], stem_mode=model["stem_mode"],
        bn_mode=model["bn_mode"])


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import resnet

    cfg = _model_cfg(model)
    batch, hw = workload["batch"], workload["hw"]

    @jax.jit
    def make_batch(key):
        k_img, k_lab = jax.random.split(key)
        return (jax.random.normal(k_img, (batch, hw, hw, 3), cfg.dtype),
                jax.random.randint(k_lab, (batch,), 0, cfg.num_classes))

    return Pieces(
        # one jitted call each: weights and batch are made on the device
        model_init=jax.jit(lambda key: resnet.init(key, cfg)),
        loss_fn=lambda p, s, b: resnet.loss_fn(p, s, b[0], b[1], cfg),
        optimizer=make_optimizer(model["optimizer"]),
        batch=make_batch(jax.random.key(key_seed(seed) + 1)),
        stateful=True, rows=batch)


def conv_plan(model: dict, hw: int):
    """Every convolution of the network as (name, out_side, kernel,
    c_in, c_out), in order; SAME padding, so a stride-s convolution
    gives ceil(side / s)."""
    plan = []
    width, small = model["width"], model["small_images"]
    side = hw if small else -(-hw // 2)
    plan.append(("stem", side, 3 if small else 7, 3, width))
    if not small:
        side = -(-side // 2)                        # 3x3/2 max-pool
    cin = width
    for s, n_blocks in enumerate(model["stage_sizes"]):
        inner = width * 2 ** s
        cout = inner * (4 if model["bottleneck"] else 1)
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            out = -(-side // stride)
            name = f"s{s}b{b}"
            if b == 0 and (cin != cout or s > 0):
                plan.append((name + ".proj", out, 1, cin, cout))
            if model["bottleneck"]:
                plan += [(name + ".conv1", side, 1, cin, inner),
                         (name + ".conv2", out, 3, inner, inner),
                         (name + ".conv3", out, 1, inner, cout)]
            else:
                plan += [(name + ".conv1", out, 3, cin, inner),
                         (name + ".conv2", out, 3, inner, cout)]
            side, cin = out, cout
    return plan, cin


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one image needs, forward and backward: 2 per
    multiply-add, every convolution and the classifier three times
    (forward, gradient of the input, gradient of the weights) except the
    stem, whose input needs no gradient. Batch-norm, ReLU, pooling and
    the loss are not counted."""
    plan, features = conv_plan(model, workload["hw"])
    total = 0.0
    for name, side, k, cin, cout in plan:
        forward = 2.0 * side * side * k * k * cin * cout
        total += forward * (2 if name == "stem" else 3)
    return total + 3 * 2.0 * features * model["num_classes"]
