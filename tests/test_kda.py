"""`ops/kda.py`: the delta rule with a decay a key channel — the two
kernels (interpret mode here) and the plain chunked form against a
token-by-token recurrence in float32, values and the five gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda as kd
from ray_tpu.ops.gated_delta import gated_delta_xla

F32 = jnp.float32


def recurrence(q, k, v, g, beta):
    """S' = Diag(exp(g_t)) S; S = S' + k (beta (v - S'^T k))^T; o = S^T q,
    one position at a time, from zero."""
    def one(q, k, v, g, beta):            # [T, H, .]
        def step(s, part):
            q_t, k_t, v_t, g_t, b_t = part
            s = jnp.exp(g_t)[:, :, None] * s
            s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (
                v_t - jnp.einsum("hkv,hk->hv", s, k_t)))
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
        return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]

    return jax.vmap(one)(q, k, v, g, beta)


def _inputs(b, t, h, dk, dv, seed=0, steep=2.5):
    keys = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(keys[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, dk)))
    v = jax.random.normal(keys[2], (b, t, h, dv))
    # a step's log decay from -e^-6 to -e^steep: a chunk's sum from
    # nothing to hundreds, by channel and position
    g = -jnp.exp(jax.random.uniform(keys[3], (b, t, h, dk), F32, -6, steep))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    w = jax.random.normal(keys[5], (b, t, h, dv))
    return (q, k, v, g, beta), w


def _both(fn, args, w):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a).astype(F32) * w).sum(), tuple(range(5))))(*args)


@pytest.fixture(scope="module")
def want():
    args, w = _inputs(2, 128, 2, 16, 32)
    with jax.default_matmul_precision("highest"):
        return args, w, jax.jit(recurrence)(*args), _both(recurrence, args, w)


@pytest.mark.parametrize("form", ["kda", "kda_xla"])
def test_matches_the_recurrence(want, form):
    """Two heads a grid step, key 16 and value 32 wide, two chunks:
    values to 3e-6, each gradient to 2e-5 of its largest."""
    args, w, o_want, (_, g_want) = want
    fn = getattr(kd, form)
    with jax.default_matmul_precision("highest"):
        o = jax.jit(fn)(*args)
        _, g_got = _both(fn, args, w)
    assert float(jnp.abs(o - o_want).max()) <= 3e-6
    for name, got, ref in zip("q k v g beta".split(), g_got, g_want):
        scale = float(jnp.abs(ref).max())
        assert scale > 0 and float(jnp.abs(got - ref).max()) \
            <= 2e-5 * scale, name


def test_an_odd_head_count_goes_one_head_a_step():
    """Three heads do not pair: a head a grid step, its inverse alone."""
    assert (kd.heads_a_step(3), kd.heads_a_step(32)) == (1, 2)
    args, w = _inputs(1, 64, 3, 8, 8, seed=3)
    with jax.default_matmul_precision("highest"):
        _, g_want = _both(recurrence, args, w)
        o = jax.jit(kd.kda)(*args)
        _, g_got = _both(kd.kda, args, w)
        assert float(jnp.abs(o - jax.jit(recurrence)(*args)).max()) <= 3e-6
    for got, ref in zip(g_got, g_want):
        assert float(jnp.abs(got - ref).max()) \
            <= 2e-5 * float(jnp.abs(ref).max())


def test_equal_channels_are_the_gated_delta_rule():
    """With one decay for all of a head's channels the rule IS
    `ops/gated_delta.py`'s."""
    (q, k, v, g, beta), _ = _inputs(1, 128, 2, 16, 16, seed=5)
    g = g[..., 0]
    with jax.default_matmul_precision("highest"):
        want = gated_delta_xla(q, k, v, g, beta)
        for fn in (kd.kda, kd.kda_xla):
            got = fn(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)
            np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_chunk_that_forgets_everything():
    """A chunk's sum near -1000 on half the channels and 0 on the
    others: exp(-Y_j) alone would overflow; values and gradients are
    finite and the recurrence's."""
    (q, k, v, g, beta), w = _inputs(1, 128, 2, 16, 16, seed=7)
    steep = jnp.arange(16) % 2 == 0
    g = jnp.where(steep, -16.0, 0.0) * jnp.ones_like(g)
    args = (q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        o_want = jax.jit(recurrence)(*args)
        _, g_want = _both(recurrence, args, w)
        for fn in (kd.kda, kd.kda_xla):
            o = jax.jit(fn)(*args)
            _, g_got = _both(fn, args, w)
            assert bool(jnp.isfinite(o).all())
            assert float(jnp.abs(o - o_want).max()) <= 3e-6
            for got, ref in zip(g_got, g_want):
                assert bool(jnp.isfinite(got).all())
                assert float(jnp.abs(got - ref).max()) \
                    <= 2e-5 * float(jnp.abs(ref).max()) + 1e-9


def test_bfloat16_inputs_stay_near_float32():
    """q, k, v in bfloat16 (the sums, the exponentials, the inverse and
    the state stay float32): the output within 2 % of the float32
    recurrence on the same rounded inputs."""
    (q, k, v, g, beta), _ = _inputs(1, 128, 2, 16, 16, seed=9)
    low = tuple(z.astype(jnp.bfloat16) for z in (q, k, v))
    with jax.default_matmul_precision("highest"):
        want = recurrence(*(z.astype(F32) for z in low), g, beta)
    got = jax.jit(kd.kda)(*low, g, beta)
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm((got.astype(F32) - want).ravel()) \
        / jnp.linalg.norm(want.ravel())
    assert float(err) < 0.02


def test_what_is_refused():
    (q, k, v, g, beta), _ = _inputs(1, 64, 2, 8, 8)
    for fn in (kd.kda, kd.kda_xla):
        with pytest.raises(ValueError, match="whole chunks"):
            fn(q[:, :40], k[:, :40], v[:, :40], g[:, :40], beta[:, :40])
        with pytest.raises(ValueError, match=r"\[B, T, H, K\] twice"):
            fn(q, k, v, g[..., 0], beta)      # a decay a head: not this rule
        with pytest.raises(ValueError, match=r"\[B, T, H, K\] twice"):
            fn(q, k[:, :, :1], v, g, beta)
