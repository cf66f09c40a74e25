"""The convolution in front of a recurrent rule as one operator
(`ops/short_conv.py::mixer_conv`: K taps, an optional bias, SiLU, the
unit norm of the leading q and k heads): the two kernels in interpret
mode against the plain form `mixer_conv_xla`, values and the gradients
of x, taps and bias, under `jax.checkpoint` and `jax.jit`; and the test
of the shape that sends what will not tile to the plain form."""

import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import short_conv
from ray_tpu.ops.short_conv import mixer_conv, mixer_conv_xla

f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
HEAD = 128


def _inputs(batch, t, c, k, biased, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (batch, t, c)).astype(dtype)
    taps = jax.random.uniform(keys[1], (k, c), minval=-0.6, maxval=0.6)
    bias = jax.random.normal(keys[2], (c,)) if biased else None
    return x, taps, bias, jax.random.normal(keys[3], (batch, t, c))


def _compare(batch, t, c, k, biased, n_unit, head, dtype, value_atol,
             grad_rtol, tile=short_conv.CONV_TILE):
    """Values within `value_atol`, every gradient within `grad_rtol` of
    its largest, of the plain form's; dtypes and shapes its own."""
    x, taps, bias, w = _inputs(batch, t, c, k, biased, dtype)
    args = (x, taps) + ((bias,) if biased else ())
    assert short_conv._channel_block(c, k, n_unit, head)

    def scalar(fn):         # -> (a weighted sum to differentiate, fn's values)
        return lambda *a: (lambda y: ((f32(y) * w).sum(), y))(fn(*a))

    def plain(x, taps, bias=None):
        return mixer_conv_xla(x, taps, bias, n_unit, head)

    ours = jax.checkpoint(
        lambda x, taps, bias=None: mixer_conv(x, taps, bias, n_unit, head,
                                              tile))
    wrt = tuple(range(len(args)))
    (_, got), got_grads = jax.jit(jax.value_and_grad(
        scalar(ours), wrt, has_aux=True))(*args)
    (_, want), want_grads = jax.value_and_grad(
        scalar(plain), wrt, has_aux=True)(*args)
    assert got.dtype == dtype and got.shape == x.shape
    assert float(jnp.abs(f32(got) - f32(want)).max()) <= value_atol
    for name, a, b in zip(("x", "taps", "bias"), got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        scale = float(jnp.abs(f32(b)).max())
        assert scale > 0 and float(jnp.abs(f32(a) - f32(b)).max()) \
            <= grad_rtol * scale, name


@pytest.mark.parametrize("c", [256, 1536])
@pytest.mark.parametrize("t", [48, 512, 1000, 2048])
@pytest.mark.parametrize("unit", [False, True], ids=["plain", "unit"])
@pytest.mark.parametrize("biased", [False, True], ids=["nobias", "bias"])
def test_mixer_conv_matches_its_jnp_form(biased, unit, t, c):
    """Float32 in, tiles of 256 positions: T under a tile, two tiles, a T
    that is no whole tiles (padded to four), eight tiles — from two tiles
    on both halos cross a boundary; C of one q and one k head, and of q
    and k of four heads each beside a plain block of 512. `tests/
    test_kda.py`'s tolerance: values 3e-6, a gradient 2e-5 of its
    largest."""
    n_unit = {256: 256, 1536: 1024}[c] if unit else 0
    _compare(1, t, c, 4, biased, n_unit, HEAD if unit else 0, jnp.float32,
             3e-6, 2e-5)


@pytest.mark.parametrize("biased,n_unit", [(False, 256), (True, 0)],
                         ids=["unit", "bias"])
def test_mixer_conv_in_the_cells_compute_dtype(biased, n_unit):
    """bf16 in and out, two sequences: both forms compute in float32 and
    cast once, so they part by one rounding of a value on the edge."""
    x, taps, bias, _ = _inputs(2, 80, 384, 4, biased, jnp.bfloat16)
    want = f32(mixer_conv_xla(x, taps, bias, n_unit, HEAD))
    _compare(2, 80, 384, 4, biased, n_unit, HEAD if n_unit else 0,
             jnp.bfloat16, 2.0 ** -8 * float(jnp.abs(want).max()), 2.0 ** -7,
             tile=32)


@pytest.mark.parametrize("k", [2, 17])
def test_two_taps_and_as_many_as_the_halo_holds(k):
    _compare(1, 48, 128, k, True, 0, 0, jnp.float32, 3e-6, 2e-5, tile=16)


def test_the_first_tile_has_a_history_of_zeros():
    """Position 0 sees its own row under the LAST tap and nothing else,
    whatever lies in the block the first tile is handed as its history
    (the array's first rows); the gradient of x at the last position
    comes from that position's output alone."""
    x, taps, bias, _ = _inputs(1, 64, 128, 4, True, jnp.float32, seed=3)
    y = mixer_conv(x, taps, bias, tile=16)
    assert jnp.allclose(y[:, 0], jax.nn.silu(taps[3] * x[:, 0] + bias),
                        atol=1e-6)
    assert jnp.allclose(y[:, 1], jax.nn.silu(
        taps[2] * x[:, 0] + taps[3] * x[:, 1] + bias), atol=1e-6)
    dx = jax.grad(lambda x: mixer_conv(x, taps, bias, tile=16)[:, -1].sum())(x)
    assert float(jnp.abs(dx[:, :-4]).max()) == 0.0
    assert float(jnp.abs(dx[:, -4:]).min()) > 0.0


def test_a_block_is_whole_heads_and_q_k_or_neither():
    block = short_conv._channel_block
    # the cells' widths: six, four and three blocks of 2048 lanes
    assert block(12288, 4, 8192, 128) == 2048
    assert block(8192, 4, 4096, 128) == 2048
    assert block(6144, 4, 0, 0) == 2048
    # a q half narrower than a block bounds it; whole heads
    assert block(4096, 4, 2048, 128) == 1024
    assert block(768, 4, 512, 256) == 256
    assert block(1536 + 640, 4, 0, 0) == 128


@pytest.mark.parametrize("c,k,n_unit,head", [
    (96, 4, 0, 0),            # C that is no whole lane tiles
    (192, 4, 128, 16),        # a head that is not whole lanes
    (640, 4, 512, 256),       # no whole head divides C and the q half
    (256, 18, 0, 0),          # more history than the halo holds
], ids=["lanes", "head", "halves", "taps"])
def test_a_shape_that_will_not_tile_takes_the_plain_form(c, k, n_unit, head,
                                                         monkeypatch):
    assert short_conv._channel_block(c, k, n_unit, head) == 0

    def no_kernel(*a, **kw):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(short_conv, "_mixer_conv", no_kernel)
    x, taps, _, _ = _inputs(1, 32, c, k, False, jnp.float32)
    got = mixer_conv(x, taps, None, n_unit, head)
    assert jnp.array_equal(got, mixer_conv_xla(x, taps, None, n_unit, head))


def test_what_is_no_convolution_of_q_k_heads_is_refused():
    x, taps = jnp.zeros((1, 16, 1024)), jnp.zeros((4, 1024))
    for bad in [(x, taps[:, :512]),                  # taps of other channels
                (x, taps, None, 384, 128),           # a head and a half each
                (x, taps, None, 2048, 128)]:         # more than there are
        with pytest.raises(ValueError, match="one tap vector a channel; the "
                           "normalised channels are q then k"):
            mixer_conv(*bad)
