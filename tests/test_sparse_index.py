"""`ops/sparse_index.py` and the selection form of `ops.flash_attention`
against their plain forms, in interpret mode at small shapes: the index
scores' kernel, the threshold (an exact k-th largest and its ties), the
indexer's loss with its gradient, and `flash_fwd` / `flash_bwd_fused`
under a selection plane (values, gradients, the tile counts, a skipped
empty tile)."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, sparse_index

B, T, HEADS, DIM, TOPK = 2, 128, 4, 16, 32
TILE = (16, 32)


@functools.cache
def _indexer(tied: bool = False):
    keys = jax.random.split(jax.random.key(0), 3)
    q_i = jax.random.normal(keys[0], (B, T, HEADS, DIM))
    k_i = jax.random.normal(keys[1], (B, T, DIM))
    w = jax.random.normal(keys[2], (B, T, HEADS))
    if tied:    # keys that score alike: planted ties at every threshold
        k_i = k_i.at[:, 1::2].set(k_i[:, 0::2])
    return q_i, k_i, w


@functools.cache
def _qkv(h_kv: int = 2):
    keys = jax.random.split(jax.random.key(1), 3)
    return (jax.random.normal(keys[0], (B, T, 4, 16)),
            jax.random.normal(keys[1], (B, T, h_kv, 16)),
            jax.random.normal(keys[2], (B, T, h_kv, 16)))


def test_index_scores_kernel_is_the_plain_sum():
    q_i, k_i, w = _indexer()
    rows = 64
    q_strip = q_i[:, rows:2 * rows].transpose(0, 2, 1, 3)
    w_strip = w[:, rows:2 * rows].transpose(0, 2, 1)[..., None]
    got = sparse_index.index_scores(q_strip, w_strip, k_i, jnp.int32(rows),
                                    TILE)
    want = sparse_index.index_scores_xla(q_i[:, rows:2 * rows], k_i,
                                         w[:, rows:2 * rows])
    causal = np.arange(T)[None, :] <= (rows + np.arange(rows))[:, None]
    assert np.isneginf(np.asarray(got)[:, ~causal]).all()
    np.testing.assert_allclose(np.asarray(got)[:, causal],
                               np.asarray(want)[:, causal], atol=2e-5)


def test_kth_largest_and_tie_bound_are_exact():
    x = jax.random.normal(jax.random.key(3), (8, 256))
    x = x.at[:, ::3].set(0.5).at[4:].multiply(-1.0).at[7].set(0.0)
    key = sparse_index.monotone_key(x)
    order = np.sort(np.asarray(x), axis=1)[:, ::-1]
    assert (np.diff(np.sort(np.asarray(key), axis=1)[:, ::-1]) <= 0).all()
    for k in (1, 7, 86, 255, 256):
        tau = sparse_index.kth_largest(key, jnp.full((8,), k, jnp.int32))
        assert np.array_equal(
            np.asarray(tau), np.asarray(sparse_index.monotone_key(
                jnp.asarray(order[:, k - 1]))))
    tied = x == 0.5
    for need in (1, 2, 40):
        last = sparse_index.tie_bound(tied, jnp.full((8,), need, jnp.int32))
        want = np.argmax(np.cumsum(np.asarray(tied), axis=1) == need, axis=1)
        assert np.array_equal(np.asarray(last)[:4], want[:4])


@pytest.mark.parametrize("tied", [False, True], ids=["seeded", "ties"])
def test_index_select_is_top_ks_set(tied):
    q_i, k_i, w = _indexer(tied)
    got = jax.jit(lambda *x: sparse_index.index_select(*x, TOPK, TILE))(
        q_i, k_i, w)
    want = sparse_index.index_select_xla(q_i, k_i, w, TOPK, TILE)
    for a, b in zip(got, want):     # the log-sum-exp is float32, the rest whole
        assert a.dtype == b.dtype
        if a.dtype == jnp.float32:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5)
        else:
            assert np.array_equal(np.asarray(a), np.asarray(b))
    plane, _, counts, beyond = got
    assert np.array_equal(np.asarray(plane).sum(-1)[0],
                          np.minimum(np.arange(T) + 1, TOPK))
    assert not np.triu(np.asarray(plane)[0], 1).any()
    assert int(counts.sum()) == int(plane.sum()) and (beyond > 0).all()
    # ... whatever the strip: one strip, or four
    for strip in (T, 32):
        again = sparse_index.index_select(q_i, k_i, w, TOPK, TILE, strip)
        assert np.array_equal(np.asarray(again[0]), np.asarray(plane))


def _dense_kl(q_i, k_i, w, q, k, keep, scale):
    scores = sparse_index.index_scores_xla(q_i, k_i, w)
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    s = jnp.einsum("bthd,bshd->bhts", q,
                   jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)) * scale
    p = jax.lax.stop_gradient(jax.nn.softmax(
        jnp.where(keep[:, None], s, -jnp.inf), axis=-1).mean(1))
    held = keep & (p > 0)
    return jnp.where(held, p * (jnp.log(jnp.where(held, p, 1.0))
                                - jnp.where(held, log_q, 0.0)), 0.0).sum()


@pytest.mark.parametrize("form", ["kernel", "plain", "causal"])
def test_index_kl_and_its_gradient(form, monkeypatch):
    """The kernel `index_kl` (two tiles of 64 a side, fed the masked
    attention's own log-sum-exp), the plain strips under a plane, and
    the plain strips over every causal key, each against the dense
    formula's value and XLA's derivative of it."""
    q_i, k_i, w = _indexer()
    q, k, v = _qkv()
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    plane = lse = lse_i = None
    if form != "causal":
        plane, lse_i, counts, _ = sparse_index.index_select(q_i, k_i, w,
                                                            TOPK, TILE)
        lse = attention.flash_attention(q, k, v, True, None, *TILE,
                                        selected=(plane, counts))[1]
    monkeypatch.setattr(sparse_index, "KL_TILE", 64 if form == "kernel"
                        else 7)     # 7 divides nothing: the plain form
    keep = causal if plane is None else plane != 0
    text = str(jax.make_jaxpr(lambda *x: sparse_index.index_kl(
        *x, q, k, plane, lse, lse_i, 0.25, 32))(q_i, k_i, w))
    assert ("pallas_call" in text) == (form == "kernel")
    got = jax.value_and_grad(lambda *x: sparse_index.index_kl(
        *x, q, k, plane, lse, lse_i, 0.25, 32), (0, 1, 2))(q_i, k_i, w)
    want = jax.value_and_grad(lambda *x: _dense_kl(
        *x, q, k, keep, 0.25), (0, 1, 2))(q_i, k_i, w)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(got[1], want[1]):   # gradients of up to 4: float32 sums
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)
    # no gradient reaches the attention's query and key through it
    dq, dk = jax.grad(lambda q, k: sparse_index.index_kl(
        q_i, k_i, w, q, k, plane, lse, lse_i, 0.25, 32), (0, 1))(q, k)
    assert not np.asarray(dq).any() and not np.asarray(dk).any()


def test_the_forward_rule_names_the_gradient_its_pass_made(capsys):
    """Under a gradient the three gradient arrays carry
    `KL_SAVED_ACROSS_REMAT`'s names, in the shapes the backward rule
    reads them: a checkpoint that keeps those names keeps exactly the
    three beside what it was given, and the bare call (no gradient)
    names nothing."""
    q_i, k_i, w = _indexer()
    q, k, _ = _qkv()

    def loss(*x):   # every causal key: the plain form, cheap to trace
        return sparse_index.index_kl(*x, q, k, None, None, None, 0.25, 32)

    assert " name[" not in str(jax.make_jaxpr(loss)(q_i, k_i, w))
    shapes = {"index_kl_dq": f"f32[{B},{T},{HEADS},{DIM}]",
              "index_kl_dk": f"f32[{B},{T},{DIM}]",
              "index_kl_dw": f"f32[{B},{T},{HEADS}]"}
    assert tuple(shapes) == sparse_index.KL_SAVED_ACROSS_REMAT
    jax.ad_checkpoint.print_saved_residuals(jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(
            *sparse_index.KL_SAVED_ACROSS_REMAT)), q_i, k_i, w)
    saved = [line for line in capsys.readouterr().out.splitlines()
             if "from the argument" not in line
             and "from a constant" not in line]   # (q and k, closed over)
    assert [line.split(" named ")[0] for line in saved] \
        == list(shapes.values())
    assert [line.split("'")[1] for line in saved] == list(shapes)


@pytest.mark.parametrize("h_kv", [2, 4], ids=["grouped", "equal-heads"])
def test_flash_attention_under_a_selection(h_kv):
    """Values and gradients against the dense form; batch row 1's plane
    is a window of 16 keys, so that most of its causal tiles hold no
    selected pair and are skipped."""
    q, k, v = _qkv(h_kv)
    plane, _, counts, _ = sparse_index.index_select(*_indexer(), TOPK, TILE)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    plane = plane.at[1].set(((ahead >= 0) & (ahead < 16)).astype(jnp.int8))
    tiles = attention.tile_counts(plane, *TILE)
    assert np.array_equal(np.asarray(tiles[0]), np.asarray(counts[0]))
    causal_tiles = attention.forward_tiles(T, 16, q.dtype, *TILE)[1]
    assert int((tiles[1] > 0).sum()) < causal_tiles \
        == int((tiles[0] > 0).sum())

    def flash(q, k, v, counts):
        return (attention.flash_attention(
            q, k, v, True, None, *TILE, None, None,
            (plane, counts))[0] ** 2).sum()

    def dense(q, k, v):
        return (attention._dense_selected(q, k, v, plane, 0.25)[0]
                ** 2).sum()

    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    for given in (tiles, None):
        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v, given)
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
        for a, b in zip(got[1], want[1]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)


def test_a_selection_takes_no_other_mask_and_keeps_the_plain_call():
    q, k, v = _qkv()
    plane = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), jnp.int8)), (B, T, T))
    for kw in ({"window": 16}, {"diffusion": 4}, {"causal": False}):
        with pytest.raises(ValueError, match="a selection keeps the causal"):
            attention.flash_attention(q, k, v, selected=(plane, None), **kw)
    # every causal key selected: the plain causal kernel's output
    np.testing.assert_allclose(
        np.asarray(attention.flash_attention(q, k, v, True, None, *TILE,
                                             selected=(plane, None))[0]),
        np.asarray(attention.flash_attention(q, k, v, True, None, *TILE)),
        atol=1e-6)
    # with no selection the kernels and calls trace to what the parent
    # commit (eb1e461) traced, forward and backward: sha256 (16 digits)
    # of the jaxprs, recorded there

    def plain(*x):
        return attention.flash_attention(*x, True, None, *TILE)

    def sha(jaxpr):
        return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]

    assert sha(jax.make_jaxpr(plain)(q, k, v)) == "35ba98145bd2f870"
    assert sha(jax.make_jaxpr(jax.grad(
        lambda *x: (plain(*x) ** 2).sum(), (0, 1, 2)))(q, k, v)) \
        == "0d6618cd20e2e93a"

