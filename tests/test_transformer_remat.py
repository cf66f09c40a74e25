"""The GPT family's block under `cfg.remat` keeps what the attention
kernel produced — its output and row log-sum-exp, named in
`ops.attention._fwd` — across the rematerialisation: `flash_fwd` runs
once a step, everything else in the block is recomputed, and nothing a
step computes changes. CPU, GPT-tiny, the kernels in interpret mode."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import transformer
from ray_tpu.ops import attention

CFG = transformer.TINY
BATCH, SEQ = 2, 128


@pytest.fixture(scope="module")
def params():
    return transformer.init(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0,
                              CFG.vocab_size)


def _drop_the_policy(monkeypatch):
    """The block as it was checkpointed before: no policy, everything
    recomputed. (jit and `jax.checkpoint` cache by function and shapes:
    cleared, so that no side reads the other's trace.)"""
    monkeypatch.setattr(
        transformer, "_remat_block",
        jax.checkpoint(transformer._block, static_argnums=(2,)))
    jax.clear_caches()


@pytest.fixture
def no_policy(monkeypatch):
    _drop_the_policy(monkeypatch)
    yield
    jax.clear_caches()


def _step_text(params, tokens, cfg=CFG):
    return str(jax.make_jaxpr(jax.value_and_grad(
        lambda p: transformer.loss_fn(p, tokens, cfg)))(params))


def _kernels(text):
    return {name: text.count(f"name={name}\n")
            for name in ("flash_fwd", "flash_bwd_fused")}


def test_forward_kernel_once_a_scanned_block(params, tokens):
    """The forward scan's body holds the one `flash_fwd`; the backward
    scan's body the one `flash_bwd_fused` and no forward kernel."""
    text = _step_text(params, tokens)
    assert _kernels(text) == {"flash_fwd": 1, "flash_bwd_fused": 1}
    assert text.count("scan[") == 2
    backward = text[text.index("remat2["):]
    assert "name=flash_fwd\n" not in backward
    assert "name=flash_bwd_fused\n" in backward


def test_without_the_policy_the_forward_kernel_runs_twice(
        params, tokens, no_policy):
    assert _kernels(_step_text(params, tokens)) == {
        "flash_fwd": 2, "flash_bwd_fused": 1}


def test_unrematerialised_block_has_one_of_each(params, tokens):
    cfg = dataclasses.replace(CFG, remat=False)
    assert _kernels(_step_text(params, tokens, cfg)) == {
        "flash_fwd": 1, "flash_bwd_fused": 1}


def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def test_saved_residuals_are_the_arguments_and_the_two_names(
        params, capsys):
    x = jnp.ones((BATCH, SEQ, CFG.d_model), CFG.dtype)
    jax.ad_checkpoint.print_saved_residuals(
        lambda x, p: transformer._remat_block(x, p, CFG), x, _layer(params))
    saved = capsys.readouterr().out.splitlines()
    beyond = sorted(line for line in saved if "from the argument" not in line)
    assert len(saved) > len(beyond) == 2
    out_name, lse_name = attention.SAVED_ACROSS_REMAT
    heads, width = CFG.n_heads, CFG.head_dim
    out, lse = beyond
    # the output is also the block's own value from there on, and jax
    # pins such a residual's precision: it is the named array behind one
    # `reduce_precision` to bfloat16's own bits
    assert out.startswith(f"bf16[{BATCH},{SEQ},{heads},{width}] ")
    assert f"named '{out_name}'" in out or "reduce_precision" in out
    assert lse.startswith(f"f32[{BATCH},{heads},{SEQ}] named '{lse_name}'")


def test_the_kept_output_is_the_named_one(params):
    """... and in the traced block that `reduce_precision` reads the
    named output, so what is kept is what `flash_fwd` wrote."""
    x = jnp.ones((BATCH, SEQ, CFG.d_model), CFG.dtype)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, p: transformer._remat_block(x, p, CFG).astype(
            jnp.float32).sum()))(x, _layer(params)))
    named = re.search(
        r"(\w+):bf16\[\d+,\d+,\d+,\d+\] = name\[name=flash_attention_out\]",
        text)
    assert named
    assert re.search(
        r"= reduce_precision\[\s*exponent_bits=8\s*mantissa_bits=7\s*\] "
        + named.group(1) + r"\n", text)


def _loss_and_grads(params, tokens, cfg=CFG):
    # primitive by primitive: what a whole-program compile would fuse
    # differs between a rematerialised block and a plain one, the
    # arithmetic does not
    with jax.disable_jit():
        return jax.value_and_grad(
            lambda p: transformer.loss_fn(p, tokens, cfg))(params)


def _assert_bit_equal(got, want):
    (loss, grads), (want_loss, want_grads) = got, want
    assert loss.tobytes() == want_loss.tobytes()
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, leaf), ref in zip(flat, jax.tree.leaves(want_grads)):
        assert leaf.tobytes() == ref.tobytes(), jax.tree_util.keystr(path)


@pytest.fixture(scope="module")
def kept(params, tokens):
    """Loss and gradients with the two residuals kept (what `encode`
    does), computed before anything is patched."""
    return _loss_and_grads(params, tokens)


def test_bit_equal_to_the_block_checkpointed_without_policy(
        params, tokens, kept, no_policy):
    _assert_bit_equal(kept, _loss_and_grads(params, tokens))


def test_bit_equal_to_the_block_not_rematerialised(params, tokens, kept):
    _assert_bit_equal(kept, _loss_and_grads(
        params, tokens, dataclasses.replace(CFG, remat=False)))


def _padded_text(params, cfg):
    x = jnp.ones((BATCH, SEQ, cfg.d_model), cfg.dtype)
    pad_mask = jnp.arange(SEQ)[None, :] < jnp.array([[SEQ], [SEQ // 2]])
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p: transformer.encode(p, x, cfg, pad_mask).astype(
            jnp.float32).sum()))(params))
    # the backward's `remat2` still carries the policy it was split by
    # (a function's address); it reads it no more
    return re.sub(r"policy=<function .* at 0x[0-9a-f]+>", "policy=None", text)


@pytest.mark.parametrize("causal", [False, True])
def test_padded_batch_path_is_the_policy_free_program(
        params, monkeypatch, causal):
    """`masked_attention` has no kernel and names nothing: the policy
    saves nothing there, and the traced step is the one a `jax.checkpoint`
    without policy gives, text for text."""
    cfg = dataclasses.replace(CFG, causal=causal)
    text = _padded_text(params, cfg)
    assert "pallas_call[\n" in text   # the norms' kernels, so a real trace
    assert "name=flash_fwd" not in text and " name[" not in text
    _drop_the_policy(monkeypatch)
    assert _padded_text(params, cfg) == text
    jax.clear_caches()


def test_names_lower_to_nothing_without_a_policy(monkeypatch):
    """`flash_attention` under a gradient with no policy around it
    compiles to the program it was without the names."""
    q = jax.ShapeDtypeStruct((2, 128, 4, 16), jnp.bfloat16)

    def lowered():
        jax.clear_caches()
        text = jax.jit(jax.value_and_grad(
            lambda q, k, v: attention.flash_attention(q, k, v).astype(
                jnp.float32).sum(), (0, 1, 2))).lower(q, q, q).as_text()
        # private functions are numbered by a count the two `name`
        # equations move on: `@_where_61` against `@_where_60`
        return re.sub(r"@(\w+?)_\d+\(", r"@\1(", text)

    with_names = lowered()
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    assert lowered() == with_names
    jax.clear_caches()
