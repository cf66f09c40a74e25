"""Model zoo tests (the workloads of BASELINE.json configs, tiny shapes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import bert, resnet, transformer as tfm, vit


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def test_gpt_forward_loss_grad(key):
    p = tfm.init(key, tfm.TINY)
    toks = jax.random.randint(key, (2, 32), 0, 256)
    logits = jax.jit(lambda p, t: tfm.apply(p, t, tfm.TINY))(p, toks)
    assert logits.shape == (2, 32, 256)
    assert logits.dtype == jnp.float32
    loss, g = jax.value_and_grad(
        lambda p: tfm.loss_fn(p, toks, tfm.TINY))(p)
    # ~uniform at init: loss ≈ log(vocab)
    assert abs(float(loss) - np.log(256)) < 0.5
    assert float(jnp.abs(g["blocks"]["wqkv"]).sum()) > 0


def test_gpt_logical_axes_match_params(key):
    p = tfm.init(key, tfm.TINY)
    ax = tfm.logical_axes(tfm.TINY)
    is_tup = lambda x: isinstance(x, tuple)  # noqa: E731
    s1 = jax.tree_util.tree_structure(jax.tree.map(lambda x: 0, p))
    s2 = jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, ax, is_leaf=is_tup))
    assert s1 == s2
    # every leaf's rank matches its axis tuple length
    flat_p = jax.tree.leaves(p)
    flat_ax = jax.tree.leaves(ax, is_leaf=is_tup)
    for leaf, axes in zip(flat_p, flat_ax):
        assert leaf.ndim == len(axes)


def test_gpt_train_step_reduces_loss(key):
    p = tfm.init(key, tfm.TINY)
    toks = jax.random.randint(key, (4, 64), 0, 256)

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, toks, tfm.TINY))(p)
        p = jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g)
        return p, loss

    losses = []
    for _ in range(5):
        p, loss = step(p)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_resnet18_and_50(key):
    img = jax.random.normal(key, (4, 32, 32, 3))
    lbl = jnp.array([0, 1, 2, 3])
    for mk in (resnet.resnet18, resnet.resnet50):
        cfg = mk(num_classes=10, small_images=True)
        p, s = resnet.init(key, cfg)
        (loss, new_s), g = jax.value_and_grad(
            lambda p: resnet.loss_fn(p, s, img, lbl, cfg), has_aux=True)(p)
        assert np.isfinite(float(loss))
        # batchnorm running stats updated
        assert not np.allclose(np.asarray(new_s["stem_bn"]["mean"]), 0)
        logits, _ = resnet.apply(p, s, img, cfg, train=False)
        assert logits.shape == (4, 10)


def test_vit(key):
    p = vit.init(key, vit.TINY)
    img = jax.random.normal(key, (2, 32, 32, 3))
    logits = jax.jit(lambda p, x: vit.apply(p, x, vit.TINY))(p, img)
    assert logits.shape == (2, 10)
    loss, g = jax.value_and_grad(
        lambda p: vit.loss_fn(p, img, jnp.array([1, 2]), vit.TINY))(p)
    assert np.isfinite(float(loss))
    # head_w is zero-init (standard ViT), so upstream grads are zero at
    # step 0 — check the head itself.
    assert float(jnp.abs(g["head_w"]).sum()) > 0


def test_bert(key):
    p = bert.init(key, bert.TINY)
    toks = jax.random.randint(key, (2, 32), 0, 256)
    types = jnp.zeros((2, 32), jnp.int32)
    logits, seq = bert.apply(p, toks, bert.TINY, types)
    assert logits.shape == (2, 2)
    assert seq.shape == (2, 32, 64)
    loss = float(bert.loss_fn(p, toks, jnp.array([0, 1]), bert.TINY))
    assert abs(loss - np.log(2)) < 0.3


def test_bert_pad_mask(key):
    """Padded positions must not influence the [CLS] logits."""
    p = bert.init(key, bert.TINY)
    toks = jax.random.randint(key, (2, 16), 0, 256)
    mask = jnp.concatenate(
        [jnp.ones((2, 10), bool), jnp.zeros((2, 6), bool)], axis=1)
    base, _ = bert.apply(p, toks, bert.TINY, pad_mask=mask)
    # scramble the padded tail — masked logits must be identical
    toks2 = toks.at[:, 10:].set((toks[:, 10:] + 7) % 256)
    scrambled, _ = bert.apply(p, toks2, bert.TINY, pad_mask=mask)
    np.testing.assert_allclose(np.asarray(base), np.asarray(scrambled),
                               atol=1e-5)
    # without the mask they differ
    no_mask, _ = bert.apply(p, toks2, bert.TINY)
    assert not np.allclose(np.asarray(base), np.asarray(no_mask), atol=1e-5)


def test_flash_backward_blockwise_matches_dense(key):
    """The backward kernel (one block here) equals the dense vjp."""
    from ray_tpu.ops.attention import _dense_attention, flash_attention
    q, k, v = (jax.random.normal(kx, (2, 64, 2, 16), jnp.float32)
               for kx in jax.random.split(key, 3))

    def f_flash(q, k, v):
        # the forward in 4 blocks of 16
        return flash_attention(q, k, v, True, None, 16, 16).sum()

    def f_dense(q, k, v):
        return _dense_attention(q, k, v, True, 16 ** -0.5).sum()

    g1 = jax.grad(f_flash, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_norm_gradients_analytic(key):
    """custom_vjp backward matches autodiff of the dense formula."""
    from ray_tpu.ops.layernorm import layernorm, rmsnorm
    x = jax.random.normal(key, (4, 16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64,)) + 1.0
    b = jax.random.normal(jax.random.PRNGKey(2), (64,))

    def ref_ln(x, w, b):
        m = x.mean(-1, keepdims=True)
        v = ((x - m) ** 2).mean(-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5) * w + b

    g1 = jax.grad(lambda *a: (layernorm(*a) ** 2).sum(), (0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda *a: (ref_ln(*a) ** 2).sum(), (0, 1, 2))(x, w, b)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-4)

    def ref_rms(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w

    h1 = jax.grad(lambda *a: (rmsnorm(*a) ** 2).sum(), (0, 1))(x, w)
    h2 = jax.grad(lambda *a: (ref_rms(*a) ** 2).sum(), (0, 1))(x, w)
    for a, bb in zip(h1, h2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-4)


def test_resnet_s2d_stem_is_equivalent(key):
    """stem_mode="s2d" (MLPerf space-to-depth trick, models/resnet.py
    _stem_s2d) must compute EXACTLY the standard 7x7/s2 stem — same
    params, same logits — so checkpoints/configs are interchangeable."""
    import dataclasses

    import numpy as np

    cfg_std = dataclasses.replace(resnet.resnet50(num_classes=10),
                                  dtype=jnp.float32)
    cfg_s2d = dataclasses.replace(cfg_std, stem_mode="s2d")
    params, state = resnet.init(key, cfg_std)
    x = jax.random.normal(key, (2, 224, 224, 3), jnp.float32)

    # stem conv alone: tight tolerance
    ref = resnet._conv(x, params["stem_conv"], 2)
    s2d = resnet._stem_s2d(x, params["stem_conv"], jnp.float32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(s2d),
                               atol=1e-4)

    # whole model end-to-end
    la, _ = resnet.apply(params, state, x, cfg_std, train=False)
    lb, _ = resnet.apply(params, state, x, cfg_s2d, train=False)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=3e-3)


def test_moe_transformer_forward_and_grads(key):
    """Flagship long-context MoE model: ring-attention + expert dispatch
    compose on one dp×sp×ep mesh; grads flow and the load-balance aux
    stays in a sane range."""
    from ray_tpu.models import moe_transformer as M
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel import sharding

    mesh = MeshSpec(dp=2, sp=2, ep=2).build()
    cfg = M.TINY_MOE
    params = M.init(key, cfg)
    params = jax.device_put(
        params, sharding.tree_shardings(mesh, M.logical_axes(cfg)))
    tokens = jax.random.randint(key, (4, 64), 0, cfg.vocab_size)

    apply_jit = jax.jit(lambda p, t: M.apply(p, t, cfg, mesh))
    logits, aux = apply_jit(params, tokens)
    assert logits.shape == (4, 64, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # top-1 routing over E experts: a balanced aux is ~1.0
    assert 0.5 < float(aux) < 4.0, float(aux)

    grad_jit = jax.jit(jax.value_and_grad(
        lambda p, t: M.loss_fn(p, t, cfg, mesh), has_aux=True))
    (loss, aux2), grads = grad_jit(params, tokens)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # experts receive gradient (dispatch is differentiable)
    assert float(jnp.abs(grads["blocks"]["w_in"]).sum()) > 0


def test_moe_transformer_ring_vs_ulysses(key):
    """The two SP attention variants agree inside the full model."""
    import dataclasses

    from ray_tpu.models import moe_transformer as M
    from ray_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(sp=4, ep=2).build()
    cfg_r = dataclasses.replace(M.TINY_MOE, attention="ring")
    cfg_u = dataclasses.replace(M.TINY_MOE, attention="ulysses")
    params = M.init(key, cfg_r)
    tokens = jax.random.randint(key, (2, 64), 0, cfg_r.vocab_size)
    lr, _ = jax.jit(lambda p, t: M.apply(p, t, cfg_r, mesh))(params, tokens)
    lu, _ = jax.jit(lambda p, t: M.apply(p, t, cfg_u, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lu),
                               atol=2e-4, rtol=2e-4)


def test_resnet_pallas_bn_backward_matches_xla(key):
    """bn_mode="pallas" (ops/batchnorm.py fused dual-reduction backward)
    must produce the same loss, running stats, and parameter gradients as
    the XLA BN path — it is a pure scheduling change, not a math change."""
    import dataclasses

    import numpy as np

    cfg_xla = dataclasses.replace(
        resnet.resnet18(num_classes=10, small_images=True),
        dtype=jnp.float32)
    cfg_pal = dataclasses.replace(cfg_xla, bn_mode="pallas")
    params, state = resnet.init(key, cfg_xla)
    x = jax.random.normal(key, (8, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(key, (8,), 0, 10)

    def run(cfg):
        (loss, new_state), grads = jax.value_and_grad(
            resnet.loss_fn, has_aux=True)(params, state, x, labels, cfg)
        return loss, new_state, grads

    la, sa, ga = run(cfg_xla)
    lb, sb, gb = run(cfg_pal)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-4), sa, sb)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3), ga, gb)


def test_bn_train_kernel_direct(key):
    """Direct unit check of ops.batchnorm.bn_train against hand autodiff
    on a shape that exercises the pallas tiling (C=128, M multiple of 8)
    and one that takes the unaligned fallback."""
    import numpy as np

    from ray_tpu.ops.batchnorm import bn_train

    for shape in ((4, 8, 8, 128), (3, 5, 5, 24)):
        x = jax.random.normal(key, shape, jnp.float32)
        scale = jax.random.normal(key, (shape[-1],)) * 0.1 + 1.0
        bias = jax.random.normal(key, (shape[-1],)) * 0.1

        def ref(x, scale, bias):
            m = jnp.mean(x, axis=(0, 1, 2))
            v = jnp.maximum(
                jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(m),
                0.0)
            xhat = (x - m) * jax.lax.rsqrt(v + 1e-5)
            return xhat * scale + bias

        def loss_k(x, scale, bias):
            y, _, _ = bn_train(x, scale, bias)
            return jnp.sum(jnp.sin(y))

        def loss_r(x, scale, bias):
            return jnp.sum(jnp.sin(ref(x, scale, bias)))

        va, ga = jax.value_and_grad(loss_k, argnums=(0, 1, 2))(
            x, scale, bias)
        vb, gb = jax.value_and_grad(loss_r, argnums=(0, 1, 2))(
            x, scale, bias)
        np.testing.assert_allclose(float(va), float(vb), rtol=1e-5)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)
