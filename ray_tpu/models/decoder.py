"""A decoder assembled from a layer pattern.

One period of the pattern says, layer by layer, which attention a layer
runs and which MLP; the period repeats down the depth (ROADMAP D7). The
kinds built so far:

- attention `"full"`: causal, NO positions; `"window"`: causal within a
  sliding window, rotary positions (rotate-half) on q and k. Both with
  grouped-query heads, through `ops.flash_attention` (the kernel the GPT
  family runs; rotary is applied outside it).
- MLP `"experts"`: top-k routed gated-ReLU experts without dropped
  tokens over a HELD share of the experts (`parallel/moe.py::
  dropless_moe`); the router reads the attention's input.

Norms are `ops.rmsnorm` (weight only). Block parameters are stacked
along a leading layer axis; `lax.scan` walks whole periods and the
period's layers are unrolled inside its body, so every layer's kind is
static and the depth costs one trace of a period. Each block is
rematerialised in the backward pass (`cfg.remat`). The head is untied
and the loss is taken in chunks of tokens, each chunk's logits
recomputed in the backward pass: at 16 k tokens over 38 k vocabulary
rows the float32 logits alone would be 2.5 GB.

Parameters are fp32, compute is `cfg.dtype`; the router's product, its
softmax and every norm's statistics are float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.layernorm import rmsnorm
from ray_tpu.parallel.moe import GMM_TILE, dropless_moe

ATTENTION_KINDS = ("full", "window")
MLP_KINDS = ("experts",)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    attention: tuple[str, ...]        # one period, a kind per layer
    mlp: tuple[str, ...]              # one period, a kind per layer
    window: int
    rope_theta: float
    n_experts: int                    # the router's outputs: ALL experts
    top_k: int
    d_expert: int
    held: tuple[int, int]             # (first, count): the experts held here
    rms_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_block_q: int = 256           # flash_attention's forward tiles
    attn_block_k: int = 512
    gmm_tile: int = GMM_TILE
    loss_chunk: int = 2048            # tokens whose logits exist at once

    def __post_init__(self):
        period = len(self.attention)
        if len(self.mlp) != period or self.n_layers % period:
            raise ValueError(
                f"{self.n_layers} layers are not whole periods of the "
                f"pattern {self.attention} x {self.mlp}")
        if not (set(self.attention) <= set(ATTENTION_KINDS)
                and set(self.mlp) <= set(MLP_KINDS)):
            raise ValueError(
                f"layer kinds built so far: attention {ATTENTION_KINDS}, "
                f"mlp {MLP_KINDS}")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"held {self.held} is no share of "
                             f"{self.n_experts} experts")


# Tiny configuration for tests and rehearsals: the period of four, 7-to-1
# head grouping kept as 2 query heads a key/value head.
TINY = DecoderConfig(
    vocab_size=256, n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
    head_dim=16, attention=("full", "window", "window", "window"),
    mlp=("experts",) * 4, window=16, rope_theta=1.5e6, n_experts=8,
    top_k=3, d_expert=32, held=(0, 8), attn_block_q=16, attn_block_k=32,
    gmm_tile=8, loss_chunk=32)


def init(key, cfg: DecoderConfig):
    """The parameter pytree: normal(0, init_std) matrices, norms at one;
    block parameters stacked on axis 0, experts on axis 1 (the held ones
    only)."""
    d, hd, L = cfg.d_model, cfg.head_dim, cfg.n_layers
    f, count = cfg.d_expert, cfg.held[1]
    keys = iter(jax.random.split(key, 12))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * cfg.init_std

    return {
        "embed": normal(cfg.vocab_size, d),
        "layers": {
            "norm1": jnp.ones((L, d)),
            "wq": normal(L, d, cfg.n_heads * hd),
            "wk": normal(L, d, cfg.n_kv_heads * hd),
            "wv": normal(L, d, cfg.n_kv_heads * hd),
            "wo": normal(L, cfg.n_heads * hd, d),
            "norm2": jnp.ones((L, d)),
            "router": normal(L, d, cfg.n_experts),
            "w_gate": normal(L, count, d, f),
            "w_up": normal(L, count, d, f),
            "w_down": normal(L, count, f, d),
        },
        "norm_f": jnp.ones((d,)),
        "head": normal(d, cfg.vocab_size),
    }


def rope_tables(t: int, cfg: DecoderConfig):
    """cos, sin [T, head_dim / 2] of position * theta ** (-2i / head_dim),
    float32."""
    half = cfg.head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, cos, sin):
    """Rotate-half pairing: dimension i turns with dimension i + half.
    x: [B, T, H, hd]; computed in float32, returned in x's dtype."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _layer(h, p, rope, *, cfg: DecoderConfig, attention: str, mlp: str):
    """One block. h: [B, T, D] in compute dtype -> (h', the MoE layer's
    counts)."""
    b, t, d = h.shape
    hd = cfg.head_dim
    cast = functools.partial(jnp.asarray, dtype=h.dtype)
    x = rmsnorm(h, cast(p["norm1"]), cfg.rms_eps)
    with jax.named_scope("router"):
        # the router reads the ATTENTION's input, in float32
        logits = jnp.dot(x.reshape(b * t, d).astype(jnp.float32),
                         p["router"], precision=lax.Precision.HIGHEST)
    with jax.named_scope("attention_" + attention):
        q = (x @ cast(p["wq"])).reshape(b, t, cfg.n_heads, hd)
        k = (x @ cast(p["wk"])).reshape(b, t, cfg.n_kv_heads, hd)
        v = (x @ cast(p["wv"])).reshape(b, t, cfg.n_kv_heads, hd)
        if attention == "window":
            q, k = _rope(q, *rope), _rope(k, *rope)
        a = flash_attention(q, k, v, True, None, cfg.attn_block_q,
                            cfg.attn_block_k,
                            cfg.window if attention == "window" else None)
        h = h + a.reshape(b, t, cfg.n_heads * hd) @ cast(p["wo"])
    assert mlp == "experts"
    y = rmsnorm(h, cast(p["norm2"]), cfg.rms_eps)
    m, counts = dropless_moe(
        y.reshape(b * t, d), logits, cast(p["w_gate"]), cast(p["w_up"]),
        cast(p["w_down"]), top_k=cfg.top_k, held=cfg.held,
        tile=cfg.gmm_tile)
    return h + m.reshape(b, t, d), counts


def hidden(params, tokens, cfg: DecoderConfig):
    """tokens [B, T] -> (the last block's output [B, T, D], before the
    final norm; counts stacked over layers [L, ...])."""
    period = len(cfg.attention)
    h = params["embed"][tokens].astype(cfg.dtype)
    rope = rope_tables(tokens.shape[1], cfg)
    blocks = []
    for attention, mlp in zip(cfg.attention, cfg.mlp):
        fn = functools.partial(_layer, cfg=cfg, attention=attention, mlp=mlp)
        blocks.append(jax.checkpoint(fn) if cfg.remat else fn)

    def one_period(h, p):
        counts = []
        for j, fn in enumerate(blocks):
            h, c = fn(h, jax.tree.map(lambda x: x[j], p), rope)
            counts.append(c)
        return h, jax.tree.map(lambda *xs: jnp.stack(xs), *counts)

    stacked = jax.tree.map(
        lambda x: x.reshape(cfg.n_layers // period, period, *x.shape[1:]),
        params["layers"])
    h, counts = lax.scan(one_period, h, stacked)
    return h, jax.tree.map(
        lambda x: x.reshape(cfg.n_layers, *x.shape[2:]), counts)


def apply(params, tokens, cfg: DecoderConfig):
    """tokens [B, T] -> float32 logits [B, T, vocab] (whole: for tests
    and small sizes; the loss below never builds them at once)."""
    h, _ = hidden(params, tokens, cfg)
    x = rmsnorm(h, params["norm_f"].astype(h.dtype), cfg.rms_eps)
    return jnp.dot(x, params["head"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def loss_fn(params, tokens, cfg: DecoderConfig):
    """Mean next-token cross-entropy over the B * (T - 1) positions that
    have a target -> (loss, counts). Attention runs at full T; the last
    position's logits are never formed."""
    b, t = tokens.shape
    h, counts = hidden(params, tokens, cfg)
    x = rmsnorm(h, params["norm_f"].astype(h.dtype), cfg.rms_eps)
    with jax.named_scope("logits_loss"):
        x = x[:, :-1].reshape(b * (t - 1), -1)
        targets = tokens[:, 1:].reshape(b * (t - 1))
        head = params["head"].astype(x.dtype)
        chunk = min(cfg.loss_chunk, x.shape[0])
        pad = -x.shape[0] % chunk
        x = jnp.pad(x, ((0, pad), (0, 0)))
        weight = jnp.pad(jnp.ones_like(targets, jnp.float32), (0, pad))
        targets = jnp.pad(targets, (0, pad))

        @jax.checkpoint
        def nll_sum(x, targets, weight):
            logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, targets[:, None], axis=-1)[:, 0]
            return (nll * weight).sum()

        def body(total, part):
            return total + nll_sum(*part), None

        total, _ = lax.scan(body, jnp.zeros((), jnp.float32), tuple(
            z.reshape(-1, chunk, *z.shape[1:]) for z in (x, targets, weight)))
        return total / (b * (t - 1)), counts


# ----------------------------------------------------------------------
# counters that leave the step without a sync
# ----------------------------------------------------------------------

def counters_init(cfg: DecoderConfig):
    """The model state of the operator's stateful form: `{"epoch_counters":
    {...}}`, scalars the step updates on the device, zeroed by the
    operator when an epoch starts and read once in `train.sync`, each
    onto that span under its key:

    `moe_assignments` (tokens x top_k x layers x steps),
    `moe_assignments_held` (those that fell on a held expert),
    `moe_assignments_dropped` (held ones that found no row: 0),
    `moe_expert_tokens_max` / `_mean` (the most and the mean a held
    expert got in one layer of one step, over all of them),
    `moe_experts_held` / `_total`, `moe_steps`. The sums are float32
    (exact to 2**24, then to seven digits): int32 would wrap in an epoch
    of 2**31 / (tokens x top_k x layers) steps."""
    f32 = functools.partial(jnp.zeros, (), jnp.float32)
    i32 = functools.partial(jnp.zeros, (), jnp.int32)
    return {"epoch_counters": {
        "moe_assignments": f32(), "moe_assignments_held": f32(),
        "moe_assignments_dropped": f32(), "moe_expert_tokens_max": i32(),
        "moe_expert_tokens_mean": f32(), "moe_experts_held": i32(),
        "moe_experts_total": i32(), "moe_steps": i32()}}


def stateful_loss(params, state, tokens, cfg: DecoderConfig):
    """`loss_fn` in the operator's stateful form: the step's counts go
    into the state's running ones."""
    loss, counts = loss_fn(params, tokens, cfg)
    old = state["epoch_counters"]
    steps = old["moe_steps"] + 1
    tokens_mean = counts["expert_tokens"].astype(jnp.float32).mean()
    new = {
        "moe_expert_tokens_max": jnp.maximum(
            old["moe_expert_tokens_max"], counts["expert_tokens"].max()),
        "moe_expert_tokens_mean": old["moe_expert_tokens_mean"] + (
            tokens_mean - old["moe_expert_tokens_mean"]) / steps,
        "moe_experts_held": jnp.full((), cfg.held[1], jnp.int32),
        "moe_experts_total": jnp.full((), cfg.n_experts, jnp.int32),
        "moe_steps": steps}
    for name, key in (("moe_assignments", "assignments"),
                      ("moe_assignments_held", "held"),
                      ("moe_assignments_dropped", "dropped")):
        new[name] = old[name] + counts[key].sum().astype(jnp.float32)
    return loss, {**state, "epoch_counters": new}

