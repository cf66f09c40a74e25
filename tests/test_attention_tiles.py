"""`flash_fwd`'s K loop as runs (`ops/attention.py::_key_runs`): the cut
against the dense mask — a tile a run calls unmasked is all true, a tile
of a masked run is mixed, a tile no run holds is all false, and the runs
walk every tile once, in the order of the keys — over lengths, tiles,
windows and the block-diffusion mask; the counts `forward_tiles` and a
decoder's `step_facts` give; and the kernel (interpret mode here) against
`_dense_fallback`, forward and every gradient, at shapes where a query
block HAS unmasked tiles (up to sixteen key tiles a query block: the
loop of three an iteration, the odd ones after it, the last tile
straight-line), with grouped heads and a value width of its own."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder
from ray_tpu.ops import attention


def dense_mask(t: int, window, diffusion) -> np.ndarray:
    """[t, t] booleans: `block_diffusion_mask`, or the causal-and-window
    `ahead` of `_dense_grouped`."""
    if diffusion is not None:
        return np.asarray(attention.block_diffusion_mask(t, diffusion))
    ahead = np.arange(t)[:, None] - np.arange(t)[None, :]
    keep = ahead >= 0
    if window is not None:
        keep &= ahead < window
    return keep


def runs_of(qi, t, bq, bk, window, diffusion):
    return [(int(a), int(b), masked) for a, b, masked in attention._key_runs(
        qi, t, bq, bk, window, diffusion, np)]


GRID = [(t, bq, bk, window, diffusion)
        for t in (1024, 2048, 8192)
        for bq, bk in ((256, 512), (512, 512), (128, 128), (512, 256),
                       (256, 1024))
        for window, diffusion in ((None, None), (512, None), (4096, None),
                                  (None, 4), (None, 64))
        if t < 8192 or bq != 128
        if diffusion is None or attention._diffusion_tiled(t, bq, bk)]


@pytest.mark.parametrize("t,bq,bk,window,diffusion", GRID)
def test_the_cut_is_exact_against_the_dense_mask(t, bq, bk, window,
                                                 diffusion):
    tiles = dense_mask(t, window, diffusion).reshape(
        t // bq, bq, t // bk, bk)
    whole, some = tiles.all((1, 3)), tiles.any((1, 3))
    unmasked = walked = 0
    for qi in range(t // bq):
        seen, at = np.zeros(t // bk, int), 0
        for start, stop, masked in runs_of(qi, t, bq, bk, window, diffusion):
            if start == stop:   # (an empty run starts anywhere)
                continue
            # in the order of the keys; nothing twice
            assert at <= start < stop
            at = stop
            seen[start:stop] += 1
            if masked:      # cut by the mask: some entry kept, some not
                assert (some[qi, start:stop] & ~whole[qi, start:stop]).all()
            else:
                assert whole[qi, start:stop].all()
                unmasked += stop - start
        # every tile that holds a kept entry once, no other tile
        assert (seen == some[qi]).all()
        walked += seen.sum()
    assert attention.forward_tiles(
        t, 64, jnp.bfloat16, bq, bk, window, diffusion) == (unmasked, walked)
    # under the causal mask (a window or not) the runs are one stretch,
    # and every query block's last tile is one the diagonal cuts
    if diffusion is None:
        (first, a, _), (b, c, _), (d, end, _) = attention._key_runs(
            np.arange(t // bq), t, bq, bk, window, None, np)
        assert np.all(a == b) and np.all(c == d)
        assert np.all(first <= a) and np.all(a <= c) and np.all(c < end)


def test_the_counts_the_cells_planes_have():
    """The planes the benchmark's cells run, at their tiles: what ISSUE
    60 reckoned by hand."""
    bf16 = jnp.bfloat16
    assert attention.forward_tiles(8192, 128, bf16, 256, 512) == (240, 272)
    assert attention.forward_tiles(4096, 128, bf16, 256, 512) == (56, 72)
    assert attention.forward_tiles(8192, 128, bf16, 256, 512, 4096) \
        == (168, 216)
    assert attention.forward_tiles(8192, 128, bf16, 256, 512, None, 4) \
        == (112, 160) \
        == (112, attention.diffusion_tiles(8192, 4, 256, 512)[0])
    # a window of one tile side: both tiles a query block walks are cut
    assert attention.forward_tiles(8192, 128, bf16, 256, 512, 512) == (0, 62)
    # GPT-2 at T 1024 by the rule's own 512 x 512: one tile in three
    assert attention.forward_tiles(1024, 64, bf16, None, None) == (1, 3)
    # no tile divides it: the dense path has none
    assert attention.forward_tiles(100, 64, bf16, None, None) == (0, 0)
    # the forward's walk is `window_scores`' second entry, in tiles
    assert attention.window_scores(8192, 4096, 128, bf16, 256, 512)[1] \
        == 216 * 256 * 512


def test_a_decoder_step_counts_its_forward_tiles():
    """`step_facts`: sequences x heads x layers (x the stack's walks, x
    the rematerialised forward) x the plane's, each attention kind under
    its own mask; a configuration with no such layer has none."""
    cfg = decoder.TINY      # T 64 at tiles of 16 x 32: (2, 6) a plane
    facts = decoder.step_facts(cfg, (2, 64))
    layers = sum(a in ("full", "window") for a, _ in cfg.kinds)
    planes = {kind: 2 * cfg.n_heads * 2 * sum(
        a == kind for a, _ in cfg.kinds) for kind in ("full", "window")}
    assert layers and cfg.remat is True
    want = [sum(planes[kind] * attention.forward_tiles(
        64, cfg.head_dim, cfg.dtype, 16, 32,
        cfg.window if kind == "window" else None)[i] for kind in planes)
        for i in (0, 1)]
    assert [facts["attention_tiles_unmasked"],
            facts["attention_tiles_walked"]] == want
    assert 0 < want[0] < want[1]
    once = dataclasses.replace(cfg, remat=False)
    assert decoder.step_facts(once, (2, 64))["attention_tiles_walked"] \
        == want[1] // 2
    none = dataclasses.replace(
        cfg, attention=("none",) * 4, mlp=("dense",) * 4)
    assert "attention_tiles_walked" not in decoder.step_facts(none, (2, 64))


def draw(seed, b, t, h, h_kv, d, d_v):
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (b, t, h, d)),
            jax.random.normal(kk, (b, t, h_kv, d)),
            jax.random.normal(kv, (b, t, h_kv, d_v)),
            jax.random.normal(kg, (b, t, h, d_v)))


# (T, the forward's tile, window, diffusion, heads, key/value heads, the
# score width, the value width): every one has query blocks with four
# key tiles or more, most of them unmasked
CASES = {
    "causal": (512, (32, 64), None, None, 4, 2, 32, 16),
    "causal-sixteen-tiles": (512, (32, 32), None, None, 2, 1, 16, 16),
    "causal-two-diagonal-tiles": (512, (64, 32), None, None, 2, 2, 16, 24),
    "window": (512, (32, 64), 320, None, 4, 2, 32, 16),
    "window-across-tiles": (512, (32, 32), 200, None, 4, 1, 16, 16),
    "window-of-a-tile": (256, (32, 32), 32, None, 2, 2, 16, 16),
    "diffusion": (512, (32, 32), None, 8, 4, 2, 32, 16),
    "diffusion-block-of-a-tile": (512, (32, 64), None, 32, 4, 2, 16, 16),
    "diffusion-block-spans-tiles": (512, (16, 32), None, 64, 2, 1, 16, 24),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_against_the_dense_path(case):
    t, (bq, bk), window, diffusion, h, h_kv, d, d_v = CASES[case]
    q, k, v, g = draw(7, 2, t, h, h_kv, d, d_v)
    # the shape has what the case is for: unmasked tiles, several a block
    unmasked, walked = attention.forward_tiles(
        t, d, q.dtype, bq, bk, window, diffusion)
    most = max(int(np.max(stop - start))
               for start, stop, masked in attention._key_runs(
                   np.arange(t // bq), t, bq, bk, window, diffusion, np)
               if not masked)
    if case != "window-of-a-tile":
        assert 0 < unmasked < walked and most >= 4

    def ours(q, k, v):
        return attention.flash_attention(q, k, v, True, None, bq, bk, window,
                                         diffusion)

    def dense(q, k, v):
        return attention._dense_fallback(q, k, v, True, d ** -0.5, window,
                                         diffusion)

    out, vjp = jax.vjp(ours, q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    assert out.shape == want.shape == (2, t, h, d_v)
    assert float(jnp.abs(out - want).max()) <= 3e-6
    assert float(jnp.abs(ours(q, k, v) - want).max()) <= 3e-6   # no lse
    for got, ref in zip(vjp(g), want_vjp(g)):
        assert got.shape == ref.shape
        assert float(jnp.abs(got - ref).max()) <= 3e-5
