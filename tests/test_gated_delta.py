"""`ops/gated_delta.py`: the kernels (interpret mode here) and the plain
chunked form against the gated delta rule walked position by position —
no chunks, no inverse — values and every gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import gated_delta as gd

F32 = jnp.float32


def recurrence(q, k, v, g, beta, initial_state=None):
    """S' = exp(g_t) S; S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T; o_t =
    S_t^T q_t, a `lax.scan` over t. Shapes as `gated_delta`'s."""
    batch, t, h, dv = v.shape
    groups, dk = q.shape[2:]
    q, k = (jnp.repeat(z.astype(F32), h // groups, axis=2) for z in (q, k))

    def step(state, part):
        q_t, k_t, v_t, g_t, b_t = part          # [B, H, .]
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - read))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    if initial_state is None:
        initial_state = jnp.zeros((batch, h, dk, dv), F32)
    _, o = lax.scan(step, initial_state.astype(F32), tuple(
        z.astype(F32).swapaxes(0, 1) for z in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


def case(chunks: int, chunk: int, decay: str, seed: int = 0,
         heads: int = 2):
    """Seeded inputs: 2 sequences, 2 key heads of 8 each serving `heads`
    value heads of 16; k L2-normalised as the mixer's are. `decay`: "strong"
    (a chunk's sum far below -87: the chunk forgets everything), "weak"
    (near zero) or "mixed" (Qwen3-Next's own range, A up to 16)."""
    keys = jax.random.split(jax.random.key(seed), 7)
    b, t, groups, h, dk, dv = 2, chunks * chunk, 2, 2 * heads, 8, 16
    q = jax.random.normal(keys[0], (b, t, groups, dk), F32) * dk ** -0.5
    k = jax.random.normal(keys[1], (b, t, groups, dk), F32)
    k = k * lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    v = jax.random.normal(keys[2], (b, t, h, dv), F32)
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (b, t, h), F32))
    rate = {"strong": (8.0, 16.0), "weak": (1e-4, 1e-3),
            "mixed": (1e-3, 16.0)}[decay]
    g = -jax.random.uniform(keys[4], (b, t, h), F32, *rate) \
        * jax.nn.softplus(jax.random.normal(keys[5], (b, t, h), F32) + 1)
    w = jax.random.normal(keys[6], (b, t, h, dv), F32)
    return (q, k, v, g, beta), w


def both(fn, args, w, **kw):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a, **kw).astype(F32) * w).sum(),
        tuple(range(len(args)))))(*args)


SIZES = [(2, 16), (3, 8), (2, 64)]


@pytest.mark.parametrize("form,chunks,chunk,decay,heads", [
    (form, *size, decay, 2) for form in ("kernels", "xla") for size in SIZES
    for decay in ("strong", "weak", "mixed")] + [
    # a key head's one value head, and three: the odd head's inverse
    # runs alone beside a pair's
    ("kernels", *size, "mixed", heads) for heads in (1, 3) for size in SIZES])
def test_values_and_gradients_match_the_recurrence(form, chunks, chunk,
                                                   decay, heads):
    args, w = case(chunks, chunk, decay, heads=heads)
    fn = gd.gated_delta if form == "kernels" else gd.gated_delta_xla
    with jax.default_matmul_precision("highest"):
        want, g_want = both(recurrence, args, w)
        got, g_got = both(fn, args, w, chunk=chunk)
        np.testing.assert_allclose(
            fn(*args, chunk=chunk), recurrence(*args), rtol=2e-4, atol=2e-5)
    assert np.isfinite(float(got))
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for name, a, r in zip("q k v g beta".split(), g_got, g_want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            a, r, rtol=2e-3, atol=2e-4 * float(jnp.abs(r).max()) + 1e-6,
            err_msg=name)


def test_strong_decay_forgets_a_chunk_without_nan():
    """A chunk's running sum reaches far below what float32's exponent
    holds: exp(-y_j) alone would overflow, the masked differences do
    not."""
    args, w = case(2, 16, "strong")
    g = args[3]
    assert float(g.reshape(2, 2, 16, 4).sum(2).max()) < -100
    for fn in (gd.gated_delta, gd.gated_delta_xla):
        o, grads = both(fn, args, w, chunk=16)
        assert np.isfinite(float(o))
        assert all(np.isfinite(np.asarray(x)).all() for x in grads)


def test_the_entering_state_and_its_gradient():
    """The plain form takes the state that enters the first chunk: its
    value and its gradient against the recurrence's."""
    args, w = case(2, 8, "mixed", seed=3)
    state = jax.random.normal(jax.random.key(9), (2, 4, 8, 16), F32)

    def loss(fn, state, **kw):
        return (fn(*args, initial_state=state, **kw) * w).sum()

    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(
            lambda s: loss(recurrence, s))(state)
        got, g_got = jax.value_and_grad(
            lambda s: loss(gd.gated_delta_xla, s, chunk=8))(state)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    np.testing.assert_allclose(g_got, g_want, rtol=2e-3, atol=1e-5)
    assert float(jnp.abs(g_want).max()) > 0


def test_the_kernels_in_bfloat16_stay_near_float32():
    args, w = case(2, 16, "mixed", seed=5)
    low = tuple(z.astype(jnp.bfloat16) if i < 3 else z
                for i, z in enumerate(args))
    exact = tuple(z.astype(F32) for z in low)
    want = recurrence(*exact)
    got = gd.gated_delta(*low, chunk=16)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.abs(got.astype(F32) - want).max()
                / jnp.abs(want).max())
    assert err < 0.03, err


def test_a_forward_without_a_gradient_saves_no_state():
    args, _ = case(2, 16, "mixed")
    text = str(jax.make_jaxpr(
        lambda *a: gd.gated_delta(*a, chunk=16))(*args))
    assert "gdr_fwd" in text and "f32[2,2,4,8,16]" not in text
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: gd.gated_delta(*a, chunk=16).sum(), (0, 1, 2, 3, 4)))(
            *args))
    assert "gdr_bwd" in text and "f32[2,2,4,8,16]" in text


def test_a_ragged_length_and_wrong_shapes_raise():
    (q, k, v, g, beta), _ = case(2, 16, "mixed")
    with pytest.raises(ValueError, match="whole chunks"):
        gd.gated_delta(q[:, :24], k[:, :24], v[:, :24], g[:, :24],
                       beta[:, :24], chunk=16)
    with pytest.raises(ValueError, match="gated_delta"):
        gd.gated_delta(q, k, v, g[..., :3], beta, chunk=16)
    with pytest.raises(ValueError, match="whole chunks"):
        gd.gated_delta_xla(q, k, v, g, beta, chunk=24)


def doubling(a, eye):
    """`_inverse` as it stood before two heads shared a product: a
    squaring and a product a factor, one head at a time."""
    x = -a
    inv = eye + x
    for _ in range(gd.inverse_products(a.shape[0]) // 2):
        x = gd._dot(x, x, gd._NN, gd._HIGHEST)
        inv = inv + gd._dot(inv, x, gd._NN, gd._HIGHEST)
    return inv


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("heads", [1, 2, 3, 4])
def test_every_heads_inverse_is_the_doublings(heads, chunk):
    """Two heads side by side in the lanes against a block-diagonal
    factor: each head's Tm is what the plain doubling gives of that
    head's A (the factor's zeros may change how a sum is blocked here,
    so not bitwise), the paired heads are those `paired_heads` counts,
    and both are (I + A)^-1."""
    assert gd.paired_heads(heads, chunk) == heads - heads % 2
    assert gd.paired_heads(heads, 128) == 0
    eye = jnp.eye(chunk)
    a = [jnp.tril(jax.random.normal(jax.random.key(chunk + j),
                                    (chunk, chunk)), -1) * 0.3
         for j in range(heads)]
    got = jax.jit(gd._inverses)(a, eye)
    assert len(got) == heads
    for a_j, tm in zip(a, got):
        want = doubling(a_j, eye)
        np.testing.assert_allclose(
            tm, want, rtol=0, atol=1e-6 * float(jnp.abs(want).max()))
        np.testing.assert_allclose(
            tm, np.linalg.inv(np.asarray(eye + a_j, np.float64)),
            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk,products", [(64, 10), (16, 6), (8, 4),
                                            (2, 0)])
def test_inverse_products(chunk, products):
    """(I + A)^-1 as (I - A)(I + A^2)(I + A^4) ..: the count the FLOP
    function reads, and the product's value on a random strictly lower
    matrix."""
    assert gd.inverse_products(chunk) == products
    a = jnp.tril(jax.random.normal(jax.random.key(chunk), (chunk, chunk)),
                 -1) * 0.3
    eye = jnp.eye(chunk)
    np.testing.assert_allclose(
        gd._inverse(a, eye), np.linalg.inv(np.asarray(eye + a, np.float64)),
        rtol=1e-4, atol=1e-5)
