"""Pallas kernel tests (interpret mode on the CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import _dense_attention, flash_attention
from ray_tpu.ops.layernorm import layernorm, rmsnorm


def test_flash_attention_causal():
    rng = np.random.default_rng(0)
    b, t, h, d = 2, 64, 2, 8
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    out = flash_attention(q, k, v, True, None, 16, 16)
    ref = _dense_attention(q, k, v, True, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_full():
    rng = np.random.default_rng(1)
    b, t, h, d = 1, 32, 4, 16
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    out = flash_attention(q, k, v, False, None, 16, 16)
    ref = _dense_attention(q, k, v, False, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
# T the rule's largest tile divides, T only a smaller one does (768) or
# a multiple of it that is no power of two (1536), T below every tile
@pytest.mark.parametrize("t", [1024, 768, 1536, 64])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_attention_at_the_rules_tiles(causal, t, dtype, tol):
    """The forward with no tile given (what `models/transformer.py`
    calls) runs the kernel at `fwd_tiles`' pair, against the dense
    reference on the same inputs in float32."""
    rng = np.random.default_rng(5)
    b, h, d = 1, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)
               for _ in range(3))
    assert attention._flash_aligned(t, d, *attention.fwd_tiles(t, d, dtype))
    out = flash_attention(q, k, v, causal)
    assert out.dtype == dtype
    ref = _dense_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                           causal, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)


# (causal, T, the forward's block — None: `fwd_tiles`' — the backward's
# (block_q, block_k) as `_bwd_tiles` returns them — None: what the file
# chooses — dtype, tolerance). Every case has a scale other than 1
# (d = 8) and a random cotangent, so each catches the `delta` term
# dropped (dq and dk wrong by p * rowsum(o * do)) and the scale left off
# dq or dk; the causal cases
# catch the diagonal block left unmasked (dk, dv gain rows of later
# queries' keys); the cases of several blocks catch a query block
# skipped by the causal loop bound (`first`), which rounds differently
# with block_q above and below block_k, and a dq that forgets a key
# block's share or is not zeroed between heads.
GRAD_CASES = {
    # the case this test was before the backward was a kernel (dq only)
    "causal-1-block": (True, 16, 8, None, jnp.float32, 3e-5),
    "causal-2-blocks": (True, 32, 16, (16, 16), jnp.float32, 3e-5),
    "causal-4-key-blocks": (True, 64, 32, (32, 16), jnp.float32, 3e-5),
    "causal-4-query-blocks": (True, 64, 16, (16, 32), jnp.float32, 3e-5),
    "full-2-blocks": (False, 32, 16, (16, 16), jnp.float32, 3e-5),
    "full-4-blocks": (False, 64, 16, (16, 32), jnp.float32, 3e-5),
    # the forward writes the lse as [B*H, T / block_q, 1, block_q] rows of
    # ITS block_q and the backward reads [B, H, T] in rows of its own:
    # the forward's tile above the backward's, below it, and the rule's
    # own tile (512 at T 1024) over a backward of 256
    "fwd-tile-above-bwd": (True, 64, 32, (16, 16), jnp.float32, 3e-5),
    "fwd-tile-below-bwd": (True, 64, 16, (32, 32), jnp.float32, 3e-5),
    "full-fwd-tile-above-bwd": (False, 64, 32, (16, 32), jnp.float32, 3e-5),
    "rule-fwd-tile-above-bwd": (True, 1024, None, (256, 256), jnp.float32,
                                3e-5),
    # ... and where both files choose: 768 x 768 forward, 256 x 256 back
    "rule-tiles-T768": (True, 768, None, None, jnp.float32, 3e-5),
    # bf16 operands on the MXU (p and ds cast to it), float32 elsewhere
    "causal-bf16": (True, 64, 16, (32, 16), jnp.bfloat16, 4e-2),
    # T % 8: forward and backward both take the dense fallback
    "causal-unaligned": (True, 20, 8, None, jnp.float32, 3e-5),
}


@pytest.mark.parametrize("case", GRAD_CASES)
def test_flash_attention_grad(case, monkeypatch):
    """dq, dk AND dv of `flash_attention` against `jax.grad` of the dense
    reference (float32 throughout, but for the bf16 case, whose
    reference reads the same bf16 inputs in float32)."""
    causal, t, block, tiles, dtype, tol = GRAD_CASES[case]
    if tiles is not None:
        monkeypatch.setattr(attention, "_bwd_tiles", lambda *_: tiles)
    rng = np.random.default_rng(2)
    b, h, d = 2, 2, 8
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)
                  for _ in range(4))

    def ours(q, k, v):
        out = flash_attention(q, k, v, causal, None, block, block)
        return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum()

    def dense(q, k, v):
        return (_dense_attention(q, k, v, causal, d ** -0.5)
                * w.astype(jnp.float32)).sum()

    got = jax.grad(ours, (0, 1, 2))(q, k, v)
    want = jax.grad(dense, (0, 1, 2))(*(x.astype(jnp.float32)
                                        for x in (q, k, v)))
    for name, a, r in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(r),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("window, block", [
    (50, (32, 32)),      # no multiple of the tile: row 127's keys start
                         # at 78, its block's loop at key 32
    (50, (16, 64)),      # a key block wider than the window
    (33, (64, 16)),      # several wholly masked leading key blocks
    (200, (32, 32)),
    (None, (32, 64)),    # grouped heads alone
])
def test_forward_lse_under_a_window_and_grouped_heads(window, block):
    """What the backward rebuilds p from: the row log-sum-exp the
    forward saves under a window and grouped heads is the dense one over
    the scores the mask keeps — also for a row whose first key block in
    the kernel's loop is wholly masked (m = NEG_INF, l = the block's
    width there; the first real score rescales both to exactly 0)."""
    rng = np.random.default_rng(7)
    b, t, h, h_kv, d = 2, 256, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, t, h_kv, d)), jnp.float32)
            for _ in range(2))
    out, lse = attention._flash_fwd_impl(
        q, k, v, causal=True, scale=d ** -0.5, block_q=block[0],
        block_k=block[1], interpret=True, window=window, save_lse=True)
    kr = jnp.repeat(k, h // h_kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * d ** -0.5
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = (ahead >= 0) & (ahead < (window or t))
    want = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)
    assert lse.shape == (b, h, t) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    assert out.shape == q.shape


# (T, head size, dtype) -> the tile `_fwd_tiles` answers: the shapes the
# six cells and this tree's tests run, and T that 512 does not divide
FWD_TILE_TABLE = {
    # gpt2s_epoch, gpt2s_short_calls, gpt2l_fsdp4: [*, 1024, 12 | 20, 64]
    (1024, 64, jnp.bfloat16): (512, 512),
    (1024, 64, jnp.float32): (512, 512),      # their float32 reference
    # smallthinker_ep4_seq8k, lfm2_ep4_seq4k, were they to ask
    (8192, 128, jnp.bfloat16): (512, 512),
    (4096, 64, jnp.bfloat16): (512, 512),
    (512, 64, jnp.bfloat16): (512, 512),
    (2048, 64, jnp.bfloat16): (512, 512),
    (1536, 8, jnp.float32): (512, 512),
    (2560, 64, jnp.bfloat16): (512, 512),
    (768, 8, jnp.float32): (768, 768),        # 512 does not divide
    (2304, 64, jnp.bfloat16): (768, 768),
    (1280, 64, jnp.bfloat16): (256, 256),
    # below a tile the kernel takes min(tile, T): all of T
    (640, 64, jnp.bfloat16): (768, 768),
    (256, 64, jnp.bfloat16): (512, 512),
    (128, 16, jnp.bfloat16): (512, 512),      # transformer.TINY
    (64, 8, jnp.float32): (512, 512),
    (16, 8, jnp.float32): (512, 512),
    # never the kernel's: 128 x 128, which `_flash_aligned` refuses
    (1000, 64, jnp.bfloat16): (128, 128),
    (192, 64, jnp.bfloat16): (128, 128),
}


@pytest.mark.parametrize("t, d, dtype", FWD_TILE_TABLE, ids=lambda x: str(
    getattr(x, "__name__", x)))
def test_fwd_tiles_table(t, d, dtype):
    want = FWD_TILE_TABLE[t, d, dtype]
    assert attention._fwd_tiles(t, d, dtype) == want
    assert attention.fwd_tiles(t, d, dtype) == want
    # a caller's numbers are kept, each on its own (the decoder family
    # passes 256 x 512)
    assert attention.fwd_tiles(t, d, dtype, 256, 512) == (256, 512)
    assert attention.fwd_tiles(t, d, dtype, None, 64) == (want[0], 64)
    assert attention.fwd_tiles(t, d, dtype, 64) == (64, want[1])


# (T, head size, dtype) -> the tile `_bwd_tiles` answers: every cell's
# shape (the window is no input of the rule), and T that 512 does not
# divide, where the tile halves until it does
BWD_TILE_TABLE = {
    (1024, 64, jnp.bfloat16): (512, 512),     # the three GPT-2 cells
    (8192, 128, jnp.bfloat16): (512, 512),    # smallthinker_ep4_seq8k
    (4096, 64, jnp.bfloat16): (512, 512),     # lfm2_ep4_seq4k
    (8192, 128, jnp.float32): (512, 512),
    (768, 8, jnp.float32): (256, 256),
    (1536, 64, jnp.bfloat16): (512, 512),
    (1280, 64, jnp.bfloat16): (256, 256),
    (640, 64, jnp.bfloat16): (128, 128),
    (128, 16, jnp.bfloat16): (128, 128),      # below a tile: all of T
    (64, 16, jnp.float32): (64, 64),
    (40, 16, jnp.float32): (40, 40),          # never the kernel's: T % 8
}


@pytest.mark.parametrize("t, d, dtype", BWD_TILE_TABLE, ids=lambda x: str(
    getattr(x, "__name__", x)))
def test_bwd_tiles_table(t, d, dtype):
    block_q, block_k = attention._bwd_tiles(t, d, dtype)
    assert (block_q, block_k) == BWD_TILE_TABLE[t, d, dtype]
    assert t % block_q == 0 and t % block_k == 0


@pytest.mark.parametrize("d", [8, 12, 64, 128])
def test_fwd_tiles_move_no_shape_across_the_dense_line(d):
    """The rule sends to the kernel exactly the shapes the 128 x 128
    default sent there, and asks for no tile under 128 rows (below that
    the kernel takes all of T, as it did)."""
    for t in range(4, 4100, 4):
        block_q, block_k = attention.fwd_tiles(t, d, jnp.bfloat16)
        assert min(block_q, block_k) >= 128, t
        assert (attention._flash_aligned(t, d, block_q, block_k)
                == attention._flash_aligned(t, d, 128, 128)), t


@pytest.mark.parametrize("t, d, warns", [(1000, 64, True), (20, 8, False),
                                         (1024, 12, True)])
def test_unaligned_shapes_still_take_the_dense_path(t, d, warns, recwarn):
    """T 1000 (no tile of 128 rows divides), T 20 (T % 8) and head size
    12 (d % 8) with no tile given: no kernel in the traced program,
    forward or backward, and the warning the fallback gave from T 512."""
    x = jax.ShapeDtypeStruct((1, t, 1, d), jnp.float32)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: flash_attention(q, k, v, True).sum(), (0, 1, 2)))(
            x, x, x))
    assert "pallas_call" not in text
    said = [str(w.message) for w in recwarn
            if "not tile-aligned" in str(w.message)]
    assert bool(said) == warns
    if warns:
        assert f"seq {t} / head_dim {d}" in said[0]


def test_layernorm_matches():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 32, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(64), jnp.float32)
    b = jnp.asarray(rng.standard_normal(64), jnp.float32)
    out = layernorm(x, w, b)
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    ref = (x - mean) / jnp.sqrt(var + 1e-5) * w + b
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_rmsnorm_matches():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(128), jnp.float32)
    out = rmsnorm(x, w)
    ref = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)
