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


# (causal, T, the forward's block, the backward's (block_q, block_k) as
# `_bwd_tiles` returns them — None: what the file chooses — dtype,
# tolerance). Every case has a scale other than 1 (d = 8) and a random
# cotangent, so each catches the `delta` term dropped (dq and dk wrong by
# p * rowsum(o * do)) and the scale left off dq or dk; the causal cases
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
