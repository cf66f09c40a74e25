"""The block-diffusion mask of `ops/attention.py`: the kernels (interpret
mode here) against a dense mask built in this file from the three rules,
forward and every gradient, at lengths and tiles where a block of the
mask straddles a tile, lies inside one or spans several, and at 8 query
heads a key/value head; the loop bounds against the mask (no tile that
holds a seen score is skipped, and the count `diffusion_tiles` gives is
what the loops walk); what a noised block's output does not depend on;
and that without the mask the traced program is the one it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention


def rule_mask(length: int, block: int) -> np.ndarray:
    """[2 L, 2 L] booleans from the three rules, row by row, in Python:
    rows and keys are `[clean 0..L) ; noised 0..L)`."""
    keep = np.zeros((2 * length, 2 * length), bool)
    for i in range(length):
        for j in range(length):
            # a clean query sees the clean keys of its block and before
            keep[i, j] = j // block <= i // block
            # ... and no noised key: keep[i, length + j] stays False
            # a noised query sees the clean keys of EARLIER blocks
            keep[length + i, j] = j // block < i // block
            # ... and the noised keys of its own block, both ways
            keep[length + i, length + j] = j // block == i // block
    return keep


def dense(q, k, v, keep):
    """Plain attention under a boolean mask, float32, grouped heads."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    s = jnp.where(keep[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def draw(seed, b, t, h, h_kv, d):
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (b, t, h, d)),
            jax.random.normal(kk, (b, t, h_kv, d)),
            jax.random.normal(kv, (b, t, h_kv, d)),
            jax.random.normal(kg, (b, t, h, d)))


# (L, block, the forward's tile, the backward's tile, heads, key/value
# heads); the backward's tile is `_bwd_tiles`' (all of L up to 512),
# steered here to tiles a block straddles, fills or spans
CASES = {
    "block-inside-tile": (64, 4, (16, 32), (32, 16), 4, 2),
    "block-straddles-tiles": (48, 3, (16, 16), (16, 16), 2, 2),
    "block-spans-tiles": (64, 32, (16, 16), (16, 8), 2, 1),
    "eight-heads-a-key-head": (32, 4, (16, 16), (16, 16), 8, 1),
    "one-block-a-tile": (64, 16, (16, 16), (16, 16), 2, 2),
    "one-block": (32, 32, (16, 32), (32, 32), 2, 1),
    "the-rules-own-backward-tile": (64, 4, (32, 64), None, 2, 1),
    "dense-fallback": (20, 4, (16, 32), None, 4, 2),
}


def call(case, monkeypatch):
    length, block, (bq, bk), bwd, h, h_kv = CASES[case]
    if bwd is not None:
        monkeypatch.setattr(attention, "_bwd_tiles", lambda *a: bwd)

    def ours(q, k, v):
        return attention.flash_attention(q, k, v, True, None, bq, bk, None,
                                         block)

    return ours, draw(3, 2, 2 * length, h, h_kv, 16), rule_mask(length, block)


def test_mask_function_is_the_three_rules():
    for length, block in ((8, 4), (12, 3), (16, 16), (6, 1)):
        assert np.array_equal(
            np.asarray(attention.block_diffusion_mask(2 * length, block)),
            rule_mask(length, block))
    keep = rule_mask(64, 4)
    assert keep.sum() / keep.size == 0.25 + 4 / (4 * 64)   # 1/4 + b / 4L


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_against_the_dense_mask(case, monkeypatch):
    ours, (q, k, v, g), keep = call(case, monkeypatch)
    out, vjp = jax.vjp(ours, q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: dense(q, k, v, keep), q, k, v)
    assert float(jnp.abs(out - want).max()) <= 2e-6
    assert float(jnp.abs(ours(q, k, v) - want).max()) <= 2e-6  # no lse
    for got, ref in zip(vjp(g), want_vjp(g)):
        assert got.shape == ref.shape
        assert float(jnp.abs(got - ref).max()) <= 1e-5


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_ran_where_the_tiles_fit(case, monkeypatch):
    """All but the last case reach both kernels; the last one (L = 20:
    no tile divides it) takes the dense fallback both ways."""
    ran = []
    for name in ("_flash_call", "_flash_bwd_call"):
        def spy(*a, _real=getattr(attention, name), _name=name, **kw):
            ran.append((_name, kw["diffusion"]))
            return _real(*a, **kw)
        monkeypatch.setattr(attention, name, spy)
    ours, (q, k, v, g), _ = call(case, monkeypatch)
    jax.vjp(ours, q, k, v)[1](g)
    block = CASES[case][1]
    assert ran == ([] if case == "dense-fallback" else [
        ("_flash_call", block), ("_flash_bwd_call", block)])


@pytest.mark.parametrize("length,block,bq,bk", [
    (64, 4, 16, 32), (48, 3, 16, 16), (64, 32, 16, 16), (4096, 4, 256, 512),
    (4096, 4, 512, 512), (2048, 32, 512, 256), (96, 12, 32, 16)])
def test_loop_bounds_cover_the_mask_and_no_more_than_they_say(
        length, block, bq, bk):
    """Every tile that holds a seen score lies inside the forward's two
    K loops and the backward's two Q loops; the loops' tiles are the
    count `diffusion_tiles` gives; a quarter of the plane and a
    diagonal, not causal's half."""
    t = 2 * length
    keep = np.asarray(attention.block_diffusion_mask(t, block))
    tiles = keep.reshape(t // bq, bq, t // bk, bk).any((1, 3))
    walked = np.zeros_like(tiles)
    for qi in range(t // bq):
        _, clean, first, last = (
            int(x) for x in attention._diffusion_key_blocks(
                qi, bq, bk, length, block, np.where))
        walked[qi, :clean] = True
        walked[qi, first:last] = True
        assert clean <= first or first == last == 0
    assert not (tiles & ~walked).any()
    visited, plane = attention.diffusion_tiles(t, block, bq, bk)
    assert visited == walked.sum() and plane == tiles.size
    assert walked.sum() <= tiles.sum() + t // bq   # at most one a row more
    if length == 4096:      # the cell's plane: 31 % where causal has 53
        assert (visited, plane) == {256: (160, 512), 512: (80, 256)}[bq]
    back = np.zeros_like(tiles)
    for ki in range(t // bk):
        first, low, high = (int(x) for x in attention._diffusion_query_blocks(
            ki, bq, bk, length, block, np.where))
        back[first:length // bq, ki] = True
        back[low:high, ki] = True
    assert not (tiles & ~back).any()
    assert back.sum() <= tiles.sum() + t // bk


def test_a_noised_block_sees_its_own_noise_and_the_clean_past_only():
    """Block 2's noised rows do not move when a later clean block, its
    own clean block, or another block's noised rows change; they do
    when an earlier clean block or their own noise changes."""
    length, block = 32, 4
    q, k, v, _ = draw(5, 1, 2 * length, 2, 1, 16)
    mine = slice(length + 2 * block, length + 3 * block)

    def out(k, v):
        return attention.flash_attention(q, k, v, True, None, 16, 16, None,
                                         block)[:, mine]

    base = out(k, v)

    def moved(rows):
        bump = jnp.zeros_like(k).at[:, rows].set(1.0)
        return float(jnp.abs(out(k + bump, v + bump) - base).max())

    assert moved(slice(3 * block, length)) == 0.0           # later clean
    assert moved(slice(2 * block, 3 * block)) == 0.0        # its own clean
    assert moved(slice(length, length + 2 * block)) == 0.0  # earlier noise
    assert moved(slice(length + 3 * block, 2 * length)) == 0.0  # later noise
    assert moved(slice(0, 2 * block)) > 1e-3                # the clean past
    assert moved(mine) > 1e-3                               # its own noise


def test_refusals():
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="whole blocks"):
        attention.flash_attention(q, q, q, True, None, 16, 16, None, 5)
    with pytest.raises(ValueError, match="no window"):
        attention.flash_attention(q, q, q, True, None, 16, 16, 8, 4)
    with pytest.raises(ValueError, match="causal=True"):
        attention.flash_attention(q, q, q, False, None, 16, 16, None, 4)


def test_without_the_mask_the_trace_is_what_it_was():
    """`diffusion=None` given or left out: one jaxpr, text for text (the
    recorded hashes of tests/test_decoder_joyai.py and test_decoder_moe.py
    hold that text to the parent's)."""
    q = jnp.zeros((2, 64, 4, 16))
    kv = jnp.zeros((2, 64, 2, 16))

    def text(*more):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda q, k, v: attention.flash_attention(
                q, k, v, True, None, 16, 32, 16, *more).sum(), (0, 1, 2)))(
                    q, kv, kv))

    assert text() == text(None)
    assert text() != str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: attention.flash_attention(
            q, k, v, True, None, 16, 32, None, 4).sum(), (0, 1, 2)))(
                q, kv, kv))
