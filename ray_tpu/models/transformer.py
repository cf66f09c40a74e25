"""GPT-style transformer in pure functional JAX, sharding-annotated.

This is the flagship model family of the framework — the analog of the
torch models the reference trains via RaySGD (reference:
python/ray/util/sgd/torch/examples/, rllib/models/) — designed TPU-first:

- params are a plain pytree; every leaf has a *logical axis* tuple
  (`logical_axes`) mapped to mesh axes by `parallel.sharding.DEFAULT_RULES`,
  so dp/tp/sp/pp layouts are a rule-table change, not a model change.
- layers are stacked along a leading axis and applied with `lax.scan`
  (one trace per block → fast compiles, XLA-friendly).
- attention is `ops.flash_attention` (pallas on TPU, dense fallback on CPU);
  norms are the `ops.layernorm` pallas kernel (`ops.rmsnorm` is the
  pattern decoder's, `models/decoder.py`).
- compute dtype bfloat16 for the MXU, params fp32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import (SAVED_ACROSS_REMAT, flash_attention,
                                   masked_attention)
from ray_tpu.ops.layernorm import layernorm


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    causal: bool = True           # False → bidirectional encoder (BERT/ViT)
    tie_embeddings: bool = True
    remat: bool = True            # jax.checkpoint each block

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# GPT-2 124M (BASELINE.json configs[4]: "Serve batched GPT-2 124M").
GPT2_SMALL = TransformerConfig()
# Tiny config for tests/dryruns.
TINY = TransformerConfig(vocab_size=256, n_layers=2, n_heads=4, d_model=64,
                         d_ff=256, max_seq=128)


def _dense_init(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def init(key, cfg: TransformerConfig):
    """Build the parameter pytree. Block params are stacked on axis 0."""
    keys = jax.random.split(key, 10)
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers

    def stack(k, shape, fan_in):
        return _dense_init(k, (L, *shape), fan_in)

    params = {
        "wte": jax.random.normal(keys[0], (cfg.vocab_size, d),
                                 jnp.float32) * 0.02,
        "wpe": jax.random.normal(keys[1], (cfg.max_seq, d),
                                 jnp.float32) * 0.01,
        "blocks": {
            "ln1_w": jnp.ones((L, d)), "ln1_b": jnp.zeros((L, d)),
            "wqkv": stack(keys[2], (d, 3 * d), d),
            "wo": stack(keys[3], (d, d), d),
            "ln2_w": jnp.ones((L, d)), "ln2_b": jnp.zeros((L, d)),
            "w_in": stack(keys[4], (d, f), d),
            "b_in": jnp.zeros((L, f)),
            "w_out": stack(keys[5], (f, d), f),
            "b_out": jnp.zeros((L, d)),
        },
        "lnf_w": jnp.ones((d,)), "lnf_b": jnp.zeros((d,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(keys[6], (d, cfg.vocab_size), d)
    return params


def logical_axes(cfg: TransformerConfig):
    """Pytree of logical-axis tuples matching init()'s output.

    "layers" is the stacked-block axis (maps to pp only in the pipeline
    trainer; None otherwise); "embed"/"heads"/"mlp"/"vocab" follow
    parallel/sharding.py DEFAULT_RULES.
    """
    ax = {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            "ln1_w": ("layers", "norm"), "ln1_b": ("layers", "norm"),
            "wqkv": ("layers", "embed", "mlp"),
            "wo": ("layers", "mlp", "embed"),
            "ln2_w": ("layers", "norm"), "ln2_b": ("layers", "norm"),
            "w_in": ("layers", "embed", "mlp"),
            "b_in": ("layers", "mlp"),
            "w_out": ("layers", "mlp", "embed"),
            "b_out": ("layers", "embed"),
        },
        "lnf_w": ("norm",), "lnf_b": ("norm",),
    }
    if not cfg.tie_embeddings:
        ax["lm_head"] = ("embed", "vocab")
    return ax


def _block(x, p, cfg: TransformerConfig, pad_mask=None):
    """One pre-norm transformer block. x: [B, T, D] in compute dtype;
    pad_mask: optional [B, T] bool (True = real token)."""
    b, t, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim

    # the scopes name the block's halves in the device trace
    with jax.named_scope("attention"):
        y = layernorm(x, p["ln1_w"].astype(x.dtype),
                      p["ln1_b"].astype(x.dtype))
        qkv = y @ p["wqkv"].astype(x.dtype)                 # [B,T,3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, h, hd)
        k = k.reshape(b, t, h, hd)
        v = v.reshape(b, t, h, hd)
        if pad_mask is None:
            attn = flash_attention(q, k, v, cfg.causal)
        else:
            # masked (padded-batch) attention: dense path, key masking
            attn = masked_attention(q, k, v, pad_mask, causal=cfg.causal)
        attn = attn.reshape(b, t, d) @ p["wo"].astype(x.dtype)
        x = x + attn

    with jax.named_scope("mlp"):
        y = layernorm(x, p["ln2_w"].astype(x.dtype),
                      p["ln2_b"].astype(x.dtype))
        y = jax.nn.gelu(y @ p["w_in"].astype(x.dtype)
                        + p["b_in"].astype(x.dtype))
        y = y @ p["w_out"].astype(x.dtype) + p["b_out"].astype(x.dtype)
        return x + y


# `_block` under cfg.remat: everything in it is recomputed in the backward
# but what the attention kernel produced. Its output and row log-sum-exp
# (the backward kernel's residuals beside q, k, v) are stacked over the
# layers, so `flash_fwd` runs once a step, not twice. The padded-batch
# path has no kernel and no such names: there the policy saves nothing.
_remat_block = jax.checkpoint(
    _block, static_argnums=(2,),
    policy=jax.checkpoint_policies.save_only_these_names(
        *SAVED_ACROSS_REMAT))


def encode(params, x, cfg: TransformerConfig, pad_mask=None):
    """The shared encoder trunk: scan the stacked blocks (remat per
    cfg.remat) then final layernorm. `params` is the full tree from init()
    (uses "blocks"/"lnf_w"/"lnf_b"). Used by GPT here and by bert/vit."""
    block_fn = _remat_block if cfg.remat else _block

    def scan_body(x, p):
        return block_fn(x, p, cfg, pad_mask), None

    x, _ = lax.scan(scan_body, x, params["blocks"])
    return layernorm(x, params["lnf_w"].astype(x.dtype),
                     params["lnf_b"].astype(x.dtype))


def apply(params, tokens, cfg: TransformerConfig, pad_mask=None):
    """tokens: [B, T] int32 → logits [B, T, vocab] (fp32)."""
    b, t = tokens.shape
    x = params["wte"][tokens].astype(cfg.dtype)
    x = x + params["wpe"][:t].astype(cfg.dtype)[None]
    x = encode(params, x, cfg, pad_mask)
    with jax.named_scope("logits_loss"):
        if cfg.tie_embeddings:
            logits = x @ params["wte"].T.astype(x.dtype)
        else:
            logits = x @ params["lm_head"].astype(x.dtype)
        return logits.astype(jnp.float32)


def loss_fn(params, tokens, cfg: TransformerConfig):
    """Next-token cross-entropy. tokens: [B, T].

    Attention runs at full T (keeps the seq dim tile-aligned so the pallas
    flash kernel engages); the last position's logits are dropped after.
    """
    logits = apply(params, tokens, cfg)[:, :-1]
    with jax.named_scope("logits_loss"):
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return nll.mean()


def num_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
