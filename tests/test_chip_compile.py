"""The main-path kernels compiled at real widths for a described (not
attached) v5e:2x2 — what the chip's compiler accepts or refuses, asked
without a chip (on-chip-measurement guide, section 2.3). Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every xdist worker
imports this file. Keep these tests in this one file."""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # such a compile can be written to the persistent cache but not read
    # back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """jax.default_backend() is the CPU here, so the ops would choose
    interpret mode: steer them to Mosaic, in the test."""
    from ray_tpu.collective.backends import pallas_backend
    from ray_tpu.ops import (attention, batchnorm, gated_delta, kda,
                             layernorm, moe_gmm, short_conv, sparse_index,
                             ssd)

    for mod in (attention, batchnorm, gated_delta, kda, layernorm, moe_gmm,
                short_conv, sparse_index, ssd, pallas_backend):
        monkeypatch.setattr(mod, "is_tpu", lambda: True)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_attention_fwd(one_chip, on_tpu):
    from ray_tpu.ops import attention

    qkv = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16,
                               sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: attention.flash_attention(q, k, v, True),
        qkv, qkv, qkv)
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("under_grad", [False, True],
                         ids=["primal", "with-lse"])
def test_flash_attention_fwd_at_the_rules_tiles(one_chip, on_tpu, under_grad):
    """The forward at `gpt2s_epoch`'s shape with no tile given: one
    kernel at `fwd_tiles`' 512 x 512 — a float32 score tile, its mask and
    p of 512 x 512 in VMEM under the compiler's default limit — as the
    first forward runs it and as the rematerialised one does, which also
    writes the row log-sum-exp in rows of ITS block_q."""
    from ray_tpu.ops import attention

    b, t, h, d = 32, 1024, 12, 64
    block_q, _ = attention.fwd_tiles(t, d, jnp.bfloat16)
    assert block_q == 512
    qkv = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    if under_grad:
        def fn(q, k, v):
            out, (*_, lse) = attention._fwd(q, k, v, True, None, None, None,
                                            None)
            return out, lse
    else:
        def fn(q, k, v):
            return attention.flash_attention(q, k, v, True)
    text = _compiled_text(fn, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") == 1 and "flash_fwd" in text
    assert (f"f32[{b * h},{t // block_q},1,{block_q}]" in text) == under_grad


def _attention_grads(q, k, v, w):
    from ray_tpu.ops import attention

    return jax.grad(lambda q, k, v: (attention.flash_attention(
        q, k, v, True).astype(jnp.float32) * w).sum(), (0, 1, 2))(q, k, v)


# the GPT-2 cells' heads: small (12 of 64) and large (20 of 64), T 1024
CELL_SHAPES = [(4, 1024, 12, 64), (2, 1024, 20, 64)]


@pytest.mark.parametrize("shape", CELL_SHAPES + [
    # beyond the cells: whole q, do and a float32 dq.T of 8k x 128 a head
    # in VMEM, which the kernel's own limit has to allow
    (1, 8192, 4, 128)])
def test_flash_attention_bwd(one_chip, on_tpu, shape):
    """The plain causal path under a gradient: the forward (here also
    writing the row log-sum-exp) and ONE backward kernel, and no float32
    score tile left for XLA."""
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(_attention_grads, qkv, qkv, qkv, w)
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    b, t, h, _ = shape
    assert f"f32[{b},{h},128,{t}]" not in text   # the old backward's tile


def _rel_err(a, r):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - r)
                 / jnp.linalg.norm(r))


@pytest.mark.parametrize("shape", CELL_SHAPES)
def test_flash_attention_bwd_runs_on_the_chip(shape):
    """Runs only where the default backend is a TPU (the test tree pins
    the CPU: `chiprun -- python -m pytest --noconftest
    tests/test_chip_compile.py -k runs_on_the_chip`). dq, dk, dv of the
    kernels on bf16 inputs against the dense reference in float32 on the
    same inputs: within bf16 rounding, by the norm of the difference
    over the norm (measured 0.0042 / 0.0041 / 0.0032, PR 32; a gradient
    cast to bf16 and nothing else reads 0.0017)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import attention

    keys = jax.random.split(jax.random.key(shape[2]), 4)
    q, k, v, w = (jax.random.normal(key, shape, jnp.float32) for key in keys)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(_attention_grads)(q, k, v, w)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda q, k, v: (attention._dense_attention(
            q, k, v, True, shape[-1] ** -0.5) * w).sum(), (0, 1, 2)))(
                *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, r in zip(("dq", "dk", "dv"), got, want):
        assert _rel_err(a, r) < 0.01, (name, _rel_err(a, r))


# the expert cells' attention layers, a sequence of each: SmallThinker's
# window and full layers (28 | 4 heads of 128 at 8k), LFM2's (32 | 8 of
# 64 at 4k); each with the forward tile its decoder passes
GROUPED_SHAPES = {
    "smallthinker-window": ((1, 8192, 28, 128), 4, 4096),
    "smallthinker-full": ((1, 8192, 28, 128), 4, None),
    "lfm2-full": ((1, 4096, 32, 64), 8, None),
    # Nemotron's attention block: sixteen query heads a key/value head
    "nemotron-full": ((1, 8192, 32, 128), 2, None),
}


def _grouped_grads(window):
    from ray_tpu.ops import attention

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: (attention.flash_attention(
            q, k, v, True, None, 256, 512, window).astype(jnp.float32)
            * w).sum(), (0, 1, 2))(q, k, v)

    return grads


@pytest.mark.parametrize("case", GROUPED_SHAPES)
def test_flash_attention_window_and_grouped_heads(one_chip, on_tpu, case):
    """The expert cells' widths under a gradient: the forward kernel at
    the decoder's 256 x 512 tiles, writing the row log-sum-exp, and ONE
    backward kernel — q, do and the float32 dq.T of one query head, dk
    and dv of a whole sequence in float32, inside the kernel's own VMEM
    limit — with nothing of the scan this path had left for XLA: no
    float32 score tile of a 64-row block, no `dynamic-update-slice` into
    a float32 dk / dv carry."""
    shape, kv_heads, window = GROUPED_SHAPES[case]
    b, t, h, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    w = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(_grouped_grads(window), q, kv, kv, w)
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    assert "dynamic-update-slice" not in text and "while(" not in text
    assert f"f32[{b},{kv_heads},{h // kv_heads},64," not in text


@pytest.mark.parametrize("case", GROUPED_SHAPES)
def test_flash_attention_window_and_grouped_bwd_runs_on_the_chip(case):
    """Runs only on a TPU, as the plain path's above. dq, dk, dv of a
    window / grouped-head call on bf16 inputs against dense masked
    attention in float32 on the same inputs, a key/value head and its
    query heads at a time (the float32 scores of 28 heads at 8k would
    not fit the chip): the same measure and limit as the plain path."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import attention

    shape, kv_heads, window = GROUPED_SHAPES[case]
    b, t, h, d = shape
    group = h // kv_heads
    keys = jax.random.split(jax.random.key(h), 4)
    q, w = (jax.random.normal(key, shape, jnp.float32) for key in keys[:2])
    k, v = (jax.random.normal(key, (b, t, kv_heads, d), jnp.float32)
            for key in keys[2:])
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(_grouped_grads(window))(q, k, v, w)

    @jax.jit
    def dense_grads(q, k, v, w):   # one key/value head and its group
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda q, k, v: (attention._dense_grouped(
                q, k, v, d ** -0.5, window) * w).sum(), (0, 1, 2))(q, k, v)

    want = [dense_grads(q[:, :, i * group:(i + 1) * group].astype(
        jnp.float32), k[:, :, i:i + 1].astype(jnp.float32),
        v[:, :, i:i + 1].astype(jnp.float32),
        w[:, :, i * group:(i + 1) * group]) for i in range(kv_heads)]
    want = [jnp.concatenate(part, axis=2) for part in zip(*want)]
    errs = {name: _rel_err(a, r)
            for name, a, r in zip(("dq", "dk", "dv"), got, want)}
    print(case, errs)
    assert max(errs.values()) < 0.01, errs


# SDAR's attention layer: a sequence of 4096 positions as [clean ;
# noised] rows, 8 query heads a key/value head, blocks of 4
DIFFUSION = ((1, 8192, 32, 128), 4, 4)


def _diffusion_grads(q, k, v, w):
    from ray_tpu.ops import attention

    return jax.grad(lambda q, k, v: (attention.flash_attention(
        q, k, v, True, None, 256, 512, None, DIFFUSION[2]).astype(
            jnp.float32) * w).sum(), (0, 1, 2))(q, k, v)


def test_flash_attention_block_diffusion(one_chip, on_tpu):
    """The block-diffusion mask at the cell's widths under a gradient:
    the same two kernels, their two loops each and the mask's integer
    arithmetic (a remainder by the block length on a column of a tile)
    accepted by Mosaic, nothing left to XLA."""
    shape, kv_heads, _ = DIFFUSION
    b, t, h, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    w = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(_diffusion_grads, q, kv, kv, w)
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    assert "dynamic-update-slice" not in text and "while(" not in text


def test_flash_attention_block_diffusion_runs_on_the_chip():
    """Runs only on a TPU. The output and dq, dk, dv of the masked call
    on bf16 inputs against dense attention under `block_diffusion_mask`
    in float32, a key/value head and its eight query heads at a time:
    the measure and limit of the other paths."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import attention

    shape, kv_heads, block = DIFFUSION
    b, t, h, d = shape
    group = h // kv_heads
    keys = jax.random.split(jax.random.key(h + block), 4)
    q, w = (jax.random.normal(key, shape, jnp.float32) for key in keys[:2])
    k, v = (jax.random.normal(key, (b, t, kv_heads, d), jnp.float32)
            for key in keys[2:])
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(_diffusion_grads)(q, k, v, w)

    @jax.jit
    def dense_grads(q, k, v, w):   # one key/value head and its group
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda q, k, v: (attention._dense_grouped(
                q, k, v, d ** -0.5, None, block) * w).sum(), (0, 1, 2))(
                    q, k, v)

    want = [dense_grads(q[:, :, i * group:(i + 1) * group].astype(
        jnp.float32), k[:, :, i:i + 1].astype(jnp.float32),
        v[:, :, i:i + 1].astype(jnp.float32),
        w[:, :, i * group:(i + 1) * group]) for i in range(kv_heads)]
    want = [jnp.concatenate(part, axis=2) for part in zip(*want)]
    errs = {name: _rel_err(a, r)
            for name, a, r in zip(("dq", "dk", "dv"), got, want)}
    print("block-diffusion", errs)
    assert max(errs.values()) < 0.01, errs


# the latent mixer's call: 192-wide queries and keys, 128-wide values
LATENT = (1, 8192, 32, 192, 128)


def _latent_shapes(one_chip):
    b, t, h, d_qk, d_v = LATENT
    qk = jax.ShapeDtypeStruct((b, t, h, d_qk), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, t, h, d_v), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((b, t, h, d_v), jnp.float32, sharding=one_chip)
    return qk, v, w


@pytest.mark.parametrize("tiles", [(256, 512), (None, None)],
                         ids=["decoder-tiles", "rule-tiles"])
def test_flash_attention_two_widths_fwd(one_chip, on_tpu, tiles):
    """The forward at 8192 x 32 heads with 192-wide q and k and 128-wide
    v: one kernel, a whole sequence of k (on 256 lanes) and v in VMEM
    under the call's own limit, an output 128 wide — nothing 192 wide
    leaves it."""
    from ray_tpu.ops import attention

    b, t, h, _, d_v = LATENT
    qk, v, _ = _latent_shapes(one_chip)
    text = _compiled_text(lambda q, k, v: attention.flash_attention(
        q, k, v, True, None, *tiles), qk, qk, v)
    assert text.count("tpu_custom_call") == 1 and "flash_fwd" in text
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and " custom-call(" in line)
    assert f"bf16[{b * h},{t},{d_v}]" in call.split(" custom-call(")[0]
    assert "pad(" not in text


def test_flash_attention_two_widths_bwd(one_chip, on_tpu):
    """... and under a gradient: the forward writing the row log-sum-exp
    and ONE backward kernel whose dv is 128 wide and whose dq.T and dk
    are 192 wide; no v, o, do or dv padded to the key width: the only
    192-wide arrays are q, k, dq and dk and their folded copies."""
    import re

    b, t, h, d_qk, d_v = LATENT
    qk, v, w = _latent_shapes(one_chip)
    text = _compiled_text(_grouped_grads(None), qk, qk, v, w)
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    assert "pad(" not in text and "while(" not in text
    bwd = next(line for line in text.splitlines()
               if "flash_bwd_fused" in line and " custom-call(" in line)
    out = bwd.split(" custom-call(")[0]
    assert f"bf16[{b * h},{t},{d_v}]" in out          # dv
    assert f"bf16[{b * h},{t},{d_qk}]" in out         # dk
    assert f"bf16[{b * h},{t // 512},{d_qk},512]" in out   # dq.T
    wide = set(re.findall(r"(?:bf16|f32)\[[0-9,]*\]", text))
    wide = {x for x in wide if str(d_qk) in x.strip("]").split("[")[1]
            .split(",")}
    assert wide <= {f"bf16[{b},{t},{h},{d_qk}]", f"bf16[{b},{h},{t},{d_qk}]",
                    f"bf16[{b * h},{t},{d_qk}]",
                    f"bf16[{b * h},{t // 512},{d_qk},512]",
                    f"bf16[{b},{h},{t // 512},{d_qk},512]"}, wide


def test_flash_attention_two_widths_runs_on_the_chip():
    """Runs only on a TPU. The output and dq, dk, dv of the latent
    mixer's call on bf16 inputs against dense attention in float32, a
    block of heads at a time: the plain path's measure and limit."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import attention

    b, t, h, d_qk, d_v = LATENT
    keys = jax.random.split(jax.random.key(h), 4)
    q, k = (jax.random.normal(key, (b, t, h, d_qk), jnp.float32)
            for key in keys[:2])
    v, w = (jax.random.normal(key, (b, t, h, d_v), jnp.float32)
            for key in keys[2:])
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(_grouped_grads(None))(q, k, v, w)

    @jax.jit
    def dense_grads(q, k, v, w):
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda q, k, v: (attention._dense_attention(
                q, k, v, True, d_qk ** -0.5) * w).sum(), (0, 1, 2))(q, k, v)

    want = [dense_grads(*(x[:, :, i:i + 4].astype(jnp.float32)
                          for x in (q, k, v, w))) for i in range(0, h, 4)]
    want = [jnp.concatenate(part, axis=2) for part in zip(*want)]
    errs = {name: _rel_err(a, r)
            for name, a, r in zip(("dq", "dk", "dv"), got, want)}
    print("latent", errs)
    assert max(errs.values()) < 0.01, errs


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one-chip", "fsdp4"])
def test_gpt_step_runs_the_forward_kernel_once(topo, on_tpu, sharded):
    """A GPT step at GPT-2 small's widths (two layers), as the chip's
    compiler leaves it: the block is rematerialised but for what
    `flash_fwd` produced, so the forward loop holds the one forward
    kernel and stacks its output and log-sum-exp over the layers, and
    the backward loop holds `flash_bwd_fused` and no second forward."""
    import dataclasses

    from ray_tpu.models import transformer
    from ray_tpu.ops import partition
    from ray_tpu.parallel import mesh as meshlib

    cfg = dataclasses.replace(transformer.GPT2_SMALL, n_layers=2)
    batch, seq = 8, 1024
    params = jax.eval_shape(lambda key: transformer.init(key, cfg),
                            jax.random.key(0))
    if sharded:
        mesh = meshlib.fsdp_mesh(topo.devices)
        batch_spec = P(("data", "fsdp"))
        where = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            meshlib.fsdp_param_specs(params, mesh),
            is_leaf=lambda x: isinstance(x, P))
        rows = NamedSharding(mesh, batch_spec)
    else:
        rows = SingleDeviceSharding(topo.devices[0])
        where = jax.tree.map(lambda _: rows, params)
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        params, where)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rows)

    def step(params, tokens):
        grad = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, tokens, cfg))
        if not sharded:
            return grad(params)
        with partition.batch_sharded(mesh, batch_spec):
            return grad(params)

    text = _compiled_text(step, params, tokens)
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("flash_fwd", "flash_bwd_fused"):
        assert sum(f"/{name}/pallas_call" in k for k in kernels) == 1
    per_chip = batch // (4 if sharded else 1)
    heads, width = cfg.n_heads, cfg.head_dim
    assert f"bf16[2,{per_chip},{seq},{heads},{width}]" in text
    assert f"f32[2,{per_chip},{heads},{seq}]" in text


def test_grouped_expert_matmul_fwd_and_bwd(one_chip, on_tpu):
    """The dropless expert layer at SmallThinker's widths (16 held
    experts of 2560 -> 768, top-6 of 64) over 8 192 tokens: the forward
    products and, under grad, both backward products of each are Mosaic
    kernels the chip's compiler accepts, at every rung of the ladder of
    row counts (`parallel/moe.py::row_ladder`: five here)."""
    from ray_tpu.parallel.moe import dropless_moe, row_ladder

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(y, r, w_gate, w_up, w_down):
        out, _ = dropless_moe(y, r, w_gate, w_up, w_down, top_k=6,
                              held=(0, 16))
        return out.astype(jnp.float32).sum()

    text = _compiled_text(
        jax.grad(loss, (0, 1, 2, 3, 4)), spec((8192, 2560)),
        spec((8192, 64), jnp.float32), spec((16, 2560, 768)),
        spec((16, 2560, 768)), spec((16, 768, 2560)))
    # the six of a rung's gradient (its forward products, run again
    # inside the backward's branch, and each one's two gradients), once
    # a rung of the ladder: the step runs one rung's
    assert text.count("tpu_custom_call") == 6 * len(
        row_ladder(8192 * 6, 16))


def test_sigmoid_routed_silu_experts_fwd_and_bwd(one_chip, on_tpu):
    """The dropless expert layer at LFM2's widths (8 held experts of
    2048 -> 1792, top-4 of 32 by sigmoid scores and a selection bias,
    SiLU) over 8 192 tokens: the same six Mosaic products a rung."""
    from ray_tpu.parallel.moe import dropless_moe, row_ladder

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(y, r, w_gate, w_up, w_down, bias):
        out, _ = dropless_moe(y, r, w_gate, w_up, w_down, top_k=4,
                              held=(0, 8), activation="silu", bias=bias)
        return out.astype(jnp.float32).sum()

    text = _compiled_text(
        jax.grad(loss, (0, 1, 2, 3, 4)), spec((8192, 2048)),
        spec((8192, 32), jnp.float32), spec((8, 2048, 1792)),
        spec((8, 2048, 1792)), spec((8, 1792, 2048)),
        spec((32,), jnp.float32))
    assert text.count("tpu_custom_call") == 6 * len(row_ladder(8192 * 4, 8))


def test_ungated_experts_of_a_width_no_lane_tile_divides(one_chip, on_tpu):
    """The dropless expert layer at Nemotron's widths (8 held UNGATED
    squared-ReLU experts of 2688 -> 1856 -> 2688, top-6 of 128 by
    sigmoid scores and a selection bias) over 8 192 tokens: two grouped
    products forward (up, down: no gate half) and each one's two
    gradients, six Mosaic calls a rung of the ladder, with the whole
    1856 lanes or rows as a weight block wherever the width is the
    blocked dimension (1856 = 14.5 x 128: no lane tile divides it, and
    nothing is padded)."""
    from ray_tpu.parallel.moe import dropless_moe, row_ladder

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(y, r, w_up, w_down, bias):
        out, _ = dropless_moe(y, r, None, w_up, w_down, top_k=6,
                              held=(0, 8), activation="relu2", bias=bias,
                              scale=2.5)
        return out.astype(jnp.float32).sum()

    text = _compiled_text(
        jax.grad(loss, (0, 1, 2, 3)), spec((8192, 2688)),
        spec((8192, 128), jnp.float32), spec((8, 1856, 2688)),
        spec((8, 1856, 2688)), spec((128,), jnp.float32))
    assert text.count("tpu_custom_call") == 6 * len(row_ladder(8192 * 6, 8))
    for name in ("moe_gmm", "moe_gmm_dx", "moe_gmm_dw"):
        assert name in text


@pytest.mark.parametrize("batch", [1, 2])
def test_ssd_fwd_and_bwd(one_chip, on_tpu, batch):
    """The Mamba-2 scan at Nemotron's shapes (8 192 positions, 64 heads
    of 64 in 8 groups, state 128, chunks of 128): one Mosaic call
    forward, which writes no state; under grad the forward that saves
    each chunk's entering states ([B, 64, 64, 64, 128] float32) and one
    backward call."""
    from ray_tpu.ops import ssd

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    t, f32 = 8192, jnp.float32
    args = (spec((batch, t, 64, 64)), spec((batch, t, 64), f32),
            spec((64,), f32), spec((batch, t, 8, 128)),
            spec((batch, t, 8, 128)), spec((64,), f32))
    text = _compiled_text(ssd.ssd, *args)
    assert text.count("tpu_custom_call") == 1 and "ssd_fwd" in text
    assert f"f32[{batch},64,64,64,128]" not in text
    text = _compiled_text(
        jax.grad(lambda *a: ssd.ssd(*a).astype(f32).sum(),
                 tuple(range(6))), *args)
    assert text.count("tpu_custom_call") == 2
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert f"f32[{batch},64,64,64,128]" in text


def test_ssd_under_a_sharded_jit(topo, on_tpu):
    """Each device runs the scan on its own sequences; A and D are whole
    on every device and their gradients are summed over all."""
    from ray_tpu.ops import partition, ssd

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "fsdp"))
    rows = NamedSharding(mesh, P(("data", "fsdp")))
    whole = NamedSharding(mesh, P())

    def spec(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    t, f32, bf16 = 1024, jnp.float32, jnp.bfloat16
    args = (spec((4, t, 64, 64), bf16, rows), spec((4, t, 64), f32, rows),
            spec((64,), f32, whole), spec((4, t, 8, 128), bf16, rows),
            spec((4, t, 8, 128), bf16, rows), spec((64,), f32, whole))

    def grads(*a):
        with partition.batch_sharded(mesh, P(("data", "fsdp"))):
            return jax.grad(lambda *a: ssd.ssd(*a).astype(f32).sum(),
                            tuple(range(6)))(*a)

    text = _compiled_text(grads, *args)
    assert text.count("tpu_custom_call") == 2
    assert "bf16[1,1024,4096]" in text          # one sequence a device


def _ssd_case():
    """Seeded inputs at Nemotron's widths, 1024 positions: (x, dt, A, B,
    C, D) with dt and A in Mamba-2's own ranges."""
    keys = jax.random.split(jax.random.key(11), 6)
    b, t = 1, 1024
    x = jax.random.normal(keys[0], (b, t, 64, 64), jnp.float32)
    dt = jnp.exp(jax.random.uniform(keys[1], (b, t, 64), jnp.float32,
                                    jnp.log(1e-3), jnp.log(0.1)))
    a = -jax.random.uniform(keys[2], (64,), jnp.float32, 1, 16)
    bm = jax.random.normal(keys[3], (b, t, 8, 128), jnp.float32)
    cm = jax.random.normal(keys[4], (b, t, 8, 128), jnp.float32)
    return x, dt, a, bm, cm, jnp.ones((64,)), jax.random.normal(
        keys[5], (b, t, 64, 64), jnp.float32)


def test_ssd_runs_on_the_chip():
    """On a chip: the kernels' values and six gradients in bf16 against
    the plain chunked form in float32, 1024 positions of Nemotron's
    widths."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import ssd

    *args, w = _ssd_case()
    low = tuple(z.astype(jnp.bfloat16) if i in (0, 3, 4) else z
                for i, z in enumerate(args))
    exact = tuple(z.astype(jnp.float32) for z in low)

    def both(fn, args):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
            tuple(range(6))))(*args)

    with jax.default_matmul_precision("highest"):
        want, g_want = both(ssd.ssd_xla, exact)
    got, g_got = both(ssd.ssd, low)
    errs = {"y": abs(float(got - want)) / abs(float(want))}
    for name, g, r in zip("x dt A B C D".split(), g_got, g_want):
        errs[name] = _rel_err(g, r)
    print("ssd", errs)
    assert max(errs.values()) < 0.02, errs


def _gdr_specs(batch, t, sharding, rows=None):
    """Qwen3-Next's delta mixer: 16 key heads and 32 value heads of
    128; q, k, v in bf16, the log decay and beta in float32."""
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (spec((batch, t, 16, 128)), spec((batch, t, 16, 128)),
            spec((batch, t, 32, 128)), spec((batch, t, 32), jnp.float32),
            spec((batch, t, 32), jnp.float32))


@pytest.mark.parametrize("batch", [1, 2])
def test_gated_delta_fwd_and_bwd(one_chip, on_tpu, batch):
    """The gated delta rule at Qwen3-Next's shapes (8 192 positions in
    chunks of 64): one Mosaic call forward, which writes no state; under
    grad the forward that saves each chunk's entering states ([B, 128,
    32, 128, 128] float32) and one backward call, under the kernels' own
    names (the trace's readers match on them)."""
    from ray_tpu.ops import gated_delta

    f32 = jnp.float32
    args = _gdr_specs(batch, 8192, one_chip)
    text = _compiled_text(gated_delta.gated_delta, *args)
    assert text.count("tpu_custom_call") == 1 and "gdr_fwd" in text
    assert f"f32[{batch},128,32,128,128]" not in text
    text = _compiled_text(
        jax.grad(lambda *a: gated_delta.gated_delta(*a).astype(f32).sum(),
                 tuple(range(5))), *args)
    assert text.count("tpu_custom_call") == 2
    assert "gdr_fwd" in text and "gdr_bwd" in text
    assert f"f32[{batch},128,32,128,128]" in text
    # and no dense fallback beside them: the plain chunked form would
    # bring a triangular solve, a scan over the chunks and XLA's products
    assert "triangular-solve" not in text and "while" not in text \
        and " dot(" not in text and "convolution(" not in text


def test_gated_delta_under_a_sharded_jit(topo, on_tpu):
    """Each device runs the rule on its own sequences."""
    from ray_tpu.ops import gated_delta, partition

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "fsdp"))
    rows = NamedSharding(mesh, P(("data", "fsdp")))

    def grads(*a):
        with partition.batch_sharded(mesh, P(("data", "fsdp"))):
            return jax.grad(lambda *a: gated_delta.gated_delta(*a).astype(
                jnp.float32).sum(), tuple(range(5)))(*a)

    text = _compiled_text(grads, *_gdr_specs(4, 1024, rows))
    assert text.count("tpu_custom_call") == 2
    assert "bf16[1,1024,4096]" in text          # one sequence a device


def test_attention_at_heads_of_256_over_8k(one_chip, on_tpu):
    """Qwen3-Next's attention layer: 16 query over 2 key/value heads of
    256 at 8 192 positions. k and v of a whole sequence, double-buffered,
    are 16 MiB of VMEM here, the compiler's whole default scope: the
    forward asks for the wide limit, as it does under two widths."""
    from ray_tpu.ops import attention

    def spec(h):
        return jax.ShapeDtypeStruct((1, 8192, h, 256), jnp.bfloat16,
                                    sharding=one_chip)

    text = _compiled_text(
        jax.grad(lambda q, k, v: attention.flash_attention(
            q, k, v, True, None, 256, 512).astype(jnp.float32).sum(),
            (0, 1, 2)), spec(16), spec(2), spec(2))
    assert "flash_fwd" in text and "flash_bwd_fused" in text


def test_gated_delta_runs_on_the_chip():
    """On a chip: the kernels' values and five gradients in bf16 against
    the plain chunked form in float32, 1024 positions of Qwen3-Next's
    widths, the decay in its own range (A up to 16)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import gated_delta as gd

    keys = jax.random.split(jax.random.key(13), 7)
    b, t, f32 = 1, 1024, jnp.float32

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(keys[0], (b, t, 16, 128), f32)) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, 16, 128), f32))
    v = jax.random.normal(keys[2], (b, t, 32, 128), f32)
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (b, t, 32), f32))
    g = -jax.random.uniform(keys[4], (32,), f32, 1e-3, 16) \
        * jax.nn.softplus(jax.random.normal(keys[5], (b, t, 32), f32) + 1)
    w = jax.random.normal(keys[6], (b, t, 32, 128), f32)
    low = tuple(z.astype(jnp.bfloat16) for z in (q, k, v)) + (g, beta)
    exact = tuple(z.astype(f32) for z in low)

    def both(fn, args):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a).astype(f32) * w).sum(),
            tuple(range(5))))(*args)

    with jax.default_matmul_precision("highest"):
        _, g_want = both(gd.gated_delta_xla, exact)
        o_want = jax.jit(gd.gated_delta_xla)(*exact)
    _, g_got = both(gd.gated_delta, low)
    # the output itself, not its weighted sum: that scalar cancels and
    # read 2.7 % on the chip beside gradients at 0.3 % (PR 57)
    errs = {"o": _rel_err(jax.jit(gd.gated_delta)(*low), o_want)}
    for name, a, r in zip("q k v g beta".split(), g_got, g_want):
        errs[name] = _rel_err(a, r)
    print("gated_delta", errs)
    assert max(errs.values()) < 0.03, errs


def _kda_specs(batch, t, sharding):
    """Kimi Linear's KDA mixer: 32 heads of 128 / 128; q, k, v in bf16,
    the log decay a key channel and beta in float32."""
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    heads = spec((batch, t, 32, 128))
    return (heads, heads, heads, spec((batch, t, 32, 128), jnp.float32),
            spec((batch, t, 32), jnp.float32))


@pytest.mark.parametrize("batch", [1, 2])
def test_kda_fwd_and_bwd(one_chip, on_tpu, batch):
    """The per-channel delta rule at Kimi Linear's shapes (8 192
    positions in chunks of 64, two heads a grid step): one Mosaic call
    forward, which writes no state; under grad the forward that saves
    each chunk's entering states and one backward call, under the
    kernels' own names, and no dense fallback beside them."""
    from ray_tpu.ops import kda

    f32 = jnp.float32
    args = _kda_specs(batch, 8192, one_chip)
    text = _compiled_text(kda.kda, *args)
    assert text.count("tpu_custom_call") == 1 and "kda_fwd" in text
    assert f"f32[{batch},128,32,128,128]" not in text
    text = _compiled_text(
        jax.grad(lambda *a: kda.kda(*a).astype(f32).sum(), tuple(range(5))),
        *args)
    assert text.count("tpu_custom_call") == 2
    assert "kda_fwd" in text and "kda_bwd" in text
    assert f"f32[{batch},128,32,128,128]" in text
    assert "triangular-solve" not in text and "while" not in text \
        and " dot(" not in text and "convolution(" not in text


def test_kda_runs_on_the_chip():
    """On a chip: the kernels' values and five gradients in bf16 against
    the plain chunked form in float32, 512 positions of Kimi Linear's
    widths, the decay a channel in its own range (A up to 16, so that
    some channels lose hundreds in a chunk and others nothing)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import kda as kd

    keys = jax.random.split(jax.random.key(17), 7)
    b, t, h, f32 = 1, 512, 4, jnp.float32

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(keys[0], (b, t, h, 128), f32)) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, 128), f32))
    v = jax.random.normal(keys[2], (b, t, h, 128), f32)
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (b, t, h), f32))
    g = -jax.random.uniform(keys[4], (h, 1), f32, 1e-3, 16) \
        * jax.nn.softplus(
            jax.random.normal(keys[5], (b, t, h, 128), f32) * 2 - 2)
    w = jax.random.normal(keys[6], (b, t, h, 128), f32)
    low = tuple(z.astype(jnp.bfloat16) for z in (q, k, v)) + (g, beta)
    exact = tuple(z.astype(f32) for z in low)

    def both(fn, args):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a).astype(f32) * w).sum(),
            tuple(range(5))))(*args)

    with jax.default_matmul_precision("highest"):
        _, g_want = both(kd.kda_xla, exact)
        o_want = jax.jit(kd.kda_xla)(*exact)
    _, g_got = both(kd.kda, low)
    errs = {"o": _rel_err(jax.jit(kd.kda)(*low), o_want)}
    for name, a, r in zip("q k v g beta".split(), g_got, g_want):
        errs[name] = _rel_err(a, r)
    print("kda", errs)
    assert max(errs.values()) < 0.03, errs


def _keye_specs(one_chip, t=16384):
    """Keye's shapes at the cell's length: the attention's q, k, v (32
    / 4 / 4 heads of 128), the indexer's q_I, k_I, w (16 heads of 64,
    one key head) and a selection plane."""
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (spec((1, t, 32, 128)), spec((1, t, 4, 128)),
            spec((1, t, 4, 128)), spec((1, t, 16, 64)), spec((1, t, 64)),
            spec((1, t, 16), jnp.float32), spec((1, t, t), jnp.int8))


def test_index_select_at_the_cells_length(one_chip, on_tpu):
    """The selection of 2048 keys a query over 16 384: the scores of a
    strip are ONE Mosaic call, `index_scores`, inside the walk over the
    strips; the plane comes out int8, and no [T, T] float32 array nor a
    [T, 16, T] one is in the text."""
    from ray_tpu.ops import sparse_index

    _, _, _, q_i, k_i, w, _ = _keye_specs(one_chip)
    text = _compiled_text(
        lambda *a: sparse_index.index_select(*a, 2048, (256, 512)),
        q_i, k_i, w)
    assert text.count("tpu_custom_call") == 1 and "index_scores" in text
    assert "s8[1,16384,16384]" in text and "f32[1,512,16384]" in text
    assert "f32[1,16384,16384]" not in text
    assert "[1,16384,16,16384]" not in text \
        and "[1,16,16384,16384]" not in text


def test_flash_attention_under_a_selection_at_the_cells_length(one_chip,
                                                               on_tpu):
    """`flash_fwd` and `flash_bwd_fused` with the plane as an input at
    [1, 16 384, 32 | 4, 128]: K and V of a key head whole (16 MiB
    double-buffered) beside a query block's row of the plane's tiles
    forward; 16 MiB of float32 dk / dv scratch and a key block's column
    of the plane backward, under the kernels' own names, no dense
    fallback beside them."""
    from ray_tpu.ops import attention

    q, k, v, _, _, _, plane = _keye_specs(one_chip)

    def fwd(q, k, v, plane):
        return attention.flash_attention(q, k, v, True, None, 256, 512,
                                         selected=(plane, None))[0]

    text = _compiled_text(fwd, q, k, v, plane)
    assert text.count("tpu_custom_call") == 1 and "flash_fwd" in text
    text = _compiled_text(jax.grad(
        lambda *a: fwd(*a).astype(jnp.float32).sum(), (0, 1, 2)),
        q, k, v, plane)
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    assert "f32[1,4,8,16384,16384]" not in text     # no dense scores


def test_index_kl_at_the_cells_length(one_chip, on_tpu):
    """The indexer's loss and its gradient in one Mosaic call,
    `index_kl`, 512 x 512 tiles with every head of the attention's
    query tile in VMEM; no strip of probabilities in the text."""
    from ray_tpu.ops import sparse_index

    q, k, _, q_i, k_i, w, plane = _keye_specs(one_chip)
    lse = jax.ShapeDtypeStruct((1, 32, 16384), jnp.float32,
                               sharding=one_chip)
    lse_i = jax.ShapeDtypeStruct((1, 16384), jnp.float32, sharding=one_chip)
    text = _compiled_text(jax.value_and_grad(
        lambda q_i, k_i, w, *rest: sparse_index.index_kl(
            q_i, k_i, w, *rest, 128 ** -0.5), (0, 1, 2)),
        q_i, k_i, w, q, k, plane, lse, lse_i)
    assert text.count("tpu_custom_call") == 1 and "index_kl" in text
    assert "f32[1,4,8,64,16384]" not in text and "while" not in text


def test_index_select_and_selected_attention_run_on_the_chip():
    """On a chip: the selected SET against `lax.top_k`'s by the plain
    road (float32 inputs: no rounding parts the two), and the attention
    over it, values and gradients in bf16, against the dense form in
    float32, at 2048 tokens and 512 keys a query."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip")
    from ray_tpu.ops import attention, sparse_index

    keys = jax.random.split(jax.random.key(23), 7)
    b, t, f32 = 1, 2048, jnp.float32
    q_i = jax.random.normal(keys[0], (b, t, 16, 64), f32)
    k_i = jax.random.normal(keys[1], (b, t, 64), f32)
    # (the model's factors on w, 16 ** -0.5 * 64 ** -0.5: at unit scale
    # the scores' softmax is one key's and the loss's gradient, a
    # difference of near-equal numbers, reads 5-6 % from the plain
    # strips' on the chip)
    w = jax.random.normal(keys[2], (b, t, 16), f32) / 32
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: sparse_index.index_select(
            *a, 512, (256, 512)))(q_i, k_i, w)
        want = jax.jit(lambda *a: sparse_index.index_select_xla(
            *a, 512, (256, 512)))(q_i, k_i, w)
    agree = float((got[0] == want[0]).mean())
    picked = int(got[0].sum())
    print("index_select: planes agree on", agree, "pairs selected", picked)
    assert picked == 512 * 513 // 2 + (t - 512) * 512
    assert agree > 0.9999
    q = jax.random.normal(keys[3], (b, t, 32, 128), f32)
    k = jax.random.normal(keys[4], (b, t, 4, 128), f32)
    v = jax.random.normal(keys[5], (b, t, 4, 128), f32)
    weight = jax.random.normal(keys[6], (b, t, 32, 128), f32)
    plane = got[0]

    def both(fn, args):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a).astype(f32) * weight).sum(), (0, 1, 2)))(*args)

    with jax.default_matmul_precision("highest"):
        _, g_want = both(lambda *a: attention._dense_selected(
            *a, plane, 128 ** -0.5)[0], (q, k, v))
    low = tuple(z.astype(jnp.bfloat16) for z in (q, k, v))
    _, g_got = both(lambda *a: attention.flash_attention(
        *a, True, None, 256, 512, selected=(plane, got[2]))[0], low)
    errs = {name: _rel_err(a, r) for name, a, r in zip("qkv", g_got, g_want)}
    print("selected attention", errs)
    assert max(errs.values()) < 0.03, errs
    # the indexer's loss and gradient: the kernel, fed the attention's
    # own log-sum-exp, against the plain strips
    lse = jax.jit(lambda *a: attention.flash_attention(
        *a, True, None, 256, 512, selected=(plane, got[2]))[1])(*low)
    scale = 128 ** -0.5
    total, grads = jax.jit(lambda *a: sparse_index._kl_pass(
        *a, plane, lse, got[1], scale, None))(
            q_i.astype(jnp.bfloat16), k_i.astype(jnp.bfloat16), w, *low[:2])
    # (the same rounded inputs: dI is a difference of near-equal
    # probabilities, and inputs rounded apart move it by percents)
    want_total, want_grads = jax.jit(lambda *a: sparse_index.index_kl_xla(
        *a, plane, scale))(q_i.astype(jnp.bfloat16),
                           k_i.astype(jnp.bfloat16), w, *low[:2])
    errs = {name: _rel_err(a, r) for name, a, r in zip(
        ("dq_i", "dk_i", "dw"), grads, want_grads)}
    errs["kl"] = abs(float(total) - float(want_total)) / float(want_total)
    print("index_kl", errs, float(total) / t)
    assert max(errs.values()) < 0.03, errs


def _short_conv_grads(bcx, taps, w):
    from ray_tpu.ops import short_conv

    return jax.grad(lambda a, b: (short_conv.short_conv(a, b).astype(
        jnp.float32) * w).sum(), (0, 1))(bcx, taps)


@pytest.mark.parametrize("t", [4096, 1000])
def test_short_conv_fwd_and_bwd(one_chip, on_tpu, t):
    """The gated short convolution at LFM2's width (three streams of
    2048, 3 taps), whole tiles of 512 positions and a length that is
    padded to them: one Mosaic call forward, one backward."""
    from ray_tpu.ops import short_conv

    bcx = jax.ShapeDtypeStruct((2, t, 3 * 2048), jnp.bfloat16,
                               sharding=one_chip)
    taps = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2, t, 2048), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(short_conv.short_conv, bcx, taps)
    assert text.count("tpu_custom_call") == 1 and "short_conv" in text
    text = _compiled_text(_short_conv_grads, bcx, taps, w)
    assert text.count("tpu_custom_call") == 1 and "short_conv_bwd" in text


def test_short_conv_under_a_sharded_jit(topo, on_tpu):
    """Each device runs the convolution on its own sequences; the taps
    are whole on every device and their gradient is summed over all."""
    from ray_tpu.ops import partition
    from ray_tpu.parallel import mesh as meshlib

    mesh = meshlib.fsdp_mesh(topo.devices)
    batch_spec = P(("data", "fsdp"))
    rows = NamedSharding(mesh, batch_spec)
    bcx = jax.ShapeDtypeStruct((8, 1024, 3 * 2048), jnp.bfloat16,
                               sharding=rows)
    w = jax.ShapeDtypeStruct((8, 1024, 2048), jnp.bfloat16, sharding=rows)
    taps = jax.ShapeDtypeStruct((3, 2048), jnp.float32,
                                sharding=NamedSharding(mesh, P()))

    def declared(*args):
        with partition.batch_sharded(mesh, batch_spec):
            return _short_conv_grads(*args)

    text = _compiled_text(declared, bcx, taps, w)
    assert "short_conv_bwd" in text and "all-reduce" in text
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        _compiled_text(_short_conv_grads, bcx, taps, w)


# the three cells' convolutions, batch 2 x 8192: (C, bias, normalised
# channels, head) — [q | k | v] of the KDA and gated-delta mixers, heads
# of 128, and the state-space mixer's xBC with a bias
MIXER_CONV_SHAPES = {
    "kda": (12288, False, 8192, 128),
    "delta": (8192, False, 4096, 128),
    "ssm": (6144, True, 0, 0)}


@pytest.mark.parametrize("mixer", list(MIXER_CONV_SHAPES))
def test_mixer_conv_fwd_and_bwd(one_chip, on_tpu, mixer):
    """The recurrent mixers' convolution at the cells' widths: none takes
    the plain form; one Mosaic call forward and one backward, blocks of
    2048 lanes whatever C. (The kernels choose interpret mode by
    `short_conv.is_tpu`, read a call: `on_tpu` steers them with the
    module.)"""
    from ray_tpu.ops import short_conv

    c, biased, n_unit, head = MIXER_CONV_SHAPES[mixer]
    assert short_conv._channel_block(c, 4, n_unit, head) == 2048
    x = jax.ShapeDtypeStruct((2, 8192, c), jnp.bfloat16, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((4, c), jnp.float32, sharding=one_chip)
    bias = [jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)
            ] * biased

    def fwd(x, taps, *bias):
        return short_conv.mixer_conv(x, taps, *bias or (None,), n_unit, head)

    def grads(x, taps, w, *bias):
        return jax.grad(lambda *a: (fwd(*a).astype(jnp.float32) * w).sum(),
                        tuple(range(2 + biased)))(x, taps, *bias)

    text = _compiled_text(fwd, x, taps, *bias)
    assert text.count("tpu_custom_call") == 1 and "mixer_conv" in text
    text = _compiled_text(grads, x, taps, x, *bias)
    assert text.count("tpu_custom_call") == 1 and "mixer_conv_bwd" in text


def test_mixer_conv_under_a_sharded_jit(topo, on_tpu):
    """Each device runs the convolution on its own sequences; taps and
    bias are whole on every device and their gradients summed over all."""
    from ray_tpu.ops import partition, short_conv
    from ray_tpu.parallel import mesh as meshlib

    mesh = meshlib.fsdp_mesh(topo.devices)
    batch_spec = P(("data", "fsdp"))
    rows, whole = NamedSharding(mesh, batch_spec), NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((8, 1024, 6144), jnp.bfloat16, sharding=rows)
    taps = jax.ShapeDtypeStruct((4, 6144), jnp.float32, sharding=whole)
    bias = jax.ShapeDtypeStruct((6144,), jnp.float32, sharding=whole)

    def grads(x, taps, bias, w):
        with partition.batch_sharded(mesh, batch_spec):
            return jax.grad(lambda *a: (short_conv.mixer_conv(*a).astype(
                jnp.float32) * w).sum(), (0, 1, 2))(x, taps, bias)

    text = _compiled_text(grads, x, taps, bias, x)
    assert "mixer_conv_bwd" in text and "all-reduce" in text


@pytest.mark.parametrize("norm", ["layernorm", "rmsnorm"])
def test_norm_fwd(one_chip, on_tpu, norm):
    from ray_tpu.ops import layernorm

    x = jax.ShapeDtypeStruct((8, 1024, 768), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((768,), jnp.bfloat16, sharding=one_chip)
    if norm == "layernorm":
        text = _compiled_text(layernorm.layernorm, x, w, w)
    else:
        text = _compiled_text(layernorm.rmsnorm, x, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,c", [(256 * 112 * 112, 64), (256 * 56 * 56, 256),
                                 (256 * 14 * 14, 1024), (256 * 7 * 7, 2048)])
def test_batchnorm_bwd_sums_resnet50_shapes(one_chip, on_tpu, m, c):
    from ray_tpu.ops import batchnorm

    act = jax.ShapeDtypeStruct((m, c), jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((1, c), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x, dy, mean, inv: batchnorm._bn_bwd_sums(
            x, dy, mean, inv, interpret=False), act, act, stat, stat)
    assert "tpu_custom_call" in text  # not the XLA-reduction fallback


@pytest.mark.parametrize("shape, batch_spec", [
    # the Trainer's own mesh for one worker that leases a host's four
    # chips (parallel.mesh.fsdp_mesh): (data=1, fsdp=4), rows over both
    (None, P(("data", "fsdp"))),
    ((2, 2), P("data")),             # rows repeated over 'fsdp'
    ((4, 1), P("data")),             # register(mesh=): pure data parallel
])
def test_kernels_under_a_sharded_jit(topo, on_tpu, shape, batch_spec):
    """A Mosaic kernel cannot be partitioned by XLA; where the layer
    that shards the batch declares how (the training operator, from its
    batch_spec), each device runs it on its rows (ops/partition.py).
    Without that the compiler refuses the step."""
    from ray_tpu.ops import attention, layernorm, partition
    from ray_tpu.parallel import mesh as meshlib

    if shape is None:
        mesh = meshlib.fsdp_mesh(topo.devices)
        assert dict(mesh.shape) == {"data": 1, "fsdp": 4}
    else:
        mesh = Mesh(np.array(topo.devices).reshape(shape),
                    ("data", "fsdp"))
    rows = NamedSharding(mesh, batch_spec)
    qkv = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16, sharding=rows)
    w = jax.ShapeDtypeStruct((64,), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))

    def block(q, k, v, w):
        return layernorm.layernorm(
            attention.flash_attention(q, k, v, True), w, w)

    def declared(*args):
        with partition.batch_sharded(mesh, batch_spec):
            return block(*args)

    def declared_grad(*args):
        with partition.batch_sharded(mesh, batch_spec):
            return jax.grad(lambda *a: block(*a).astype(jnp.float32).sum(),
                            (0, 1, 2))(*args)

    assert _compiled_text(declared, qkv, qkv, qkv, w).count(
        "tpu_custom_call") == 2
    # ... and under a gradient the same backward kernel per device
    text = _compiled_text(declared_grad, qkv, qkv, qkv, w)
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        _compiled_text(block, qkv, qkv, qkv, w)


def test_pallas_ring_tier_is_refused_by_the_chips_compiler(topo, on_tpu,
                                                           monkeypatch):
    """Transport.PALLAS loads directly from ANY-space refs; Mosaic
    refuses that for a v5e, which is why pallas_supported() is False on a
    live TPU backend (the tier votes itself unavailable and never runs
    interpreted on a chip). When the kernels are rewritten to stage
    through VMEM, this test turns into a compile check."""
    from ray_tpu.collective.backends import pallas_backend
    from ray_tpu.collective.types import ReduceOp

    mesh = Mesh(np.array(topo.devices), ("ranks",))
    ops = pallas_backend._PallasOps(mesh, "ranks", 4)
    assert ops.interpret is False
    assert pallas_backend.pallas_supported.__wrapped__() is False
    spec = P("ranks", None)

    def compile_only(key, wrapper, out_specs=None):
        return lambda x: jax.jit(pallas_backend._shard_map(
            wrapper, mesh, spec,
            out_specs if out_specs is not None else spec)).lower(x).compile()

    monkeypatch.setattr(ops, "_jit", compile_only)
    x = jax.ShapeDtypeStruct((4, 4096), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    with pytest.raises(Exception, match="Loads are only allowed on VMEM"):
        ops.allreduce(x, ReduceOp.SUM)


def test_looped_decoder_step_fits_the_chip_at_the_batch_shipped(
        one_chip, on_tpu, tmp_path):
    """The cell `ouro_d8_loop4_seq4k`'s fused step (loss, gradients,
    AdamW, donated state) at the published widths and the batch its file
    ships, compiled for the described chip: at most 13.5 GiB by the
    buffer assignment (the cells' batch rule), and ONE trace of the
    period whatever the walks — the forward kernel twice (a block and
    its rematerialised copy), the backward once, not once a walk."""
    import glob
    import re

    from benchmark import manifest

    cell = manifest.cell("ouro_d8_loop4_seq4k")
    p = cell["family"].pieces(cell["model"], cell["workload"], 7)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    opt = jax.eval_shape(p.optimizer.init, params)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def fused(params, mstate, opt_state, batch):
        (loss, mstate), grads = jax.value_and_grad(
            p.loss_fn, has_aux=True)(params, mstate, batch)
        updates, opt_state = p.optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda a, u: a + u, params, updates)
        return params, mstate, opt_state, loss

    compiled = jax.jit(fused, donate_argnums=(0, 1, 2)).lower(
        on_chip(params), on_chip(state), on_chip(opt),
        on_chip(p.batch)).compile(compiler_options={
            "xla_dump_to": str(tmp_path), "xla_dump_hlo_as_text": True})
    reports = glob.glob(str(tmp_path / "*memory-usage-report.txt"))
    assert reports
    used = max(int(re.search(r"Total bytes used: (\d+)",
                             open(r).read()).group(1)) for r in reports)
    assert used <= 13.5 * 2 ** 30, used / 2 ** 30
    assert cell["workload"]["aot_step_GiB"][
        str(cell["workload"]["batch"])] == pytest.approx(used / 2 ** 30,
                                                         abs=0.05)
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("/flash_fwd/pallas_call" in k for k in kernels) == 2
    assert sum("/flash_bwd_fused/pallas_call" in k for k in kernels) == 1
