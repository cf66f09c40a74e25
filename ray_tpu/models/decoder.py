"""A decoder assembled from a layer pattern.

A layer is a MIXER and an MLP. The configuration names, layer by layer,
the kinds of the leading layers (`lead_attention`, `lead_mlp`: a model's
dense first layers, walked once) and of one period (`attention`, `mlp`),
which repeats down the rest of the depth (ROADMAP D7). The kinds built
so far:

- mixer `"full"`: causal attention; `"window"`: causal within a sliding
  window. Both with grouped-query heads, through `ops.flash_attention`
  (the kernel the GPT family runs). Rotary positions (rotate-half, on q
  and k, outside the kernel) and an RMSNorm over each head of q and k
  before them are properties of an attention kind: `cfg.rotary` and
  `cfg.qk_norm` list the kinds that have them (by default `"window"`
  turns and nothing is normed).
- mixer `"conv"`: no attention at all. An input projection to three
  streams, the gated short convolution of `ops/short_conv.py` (B * x, a
  depthwise causal convolution of `conv_taps` taps, * C), an output
  projection. Its state is `conv_taps - 1` rows a sequence, not keys
  and values.
- MLP `"experts"`: top-k routed gated experts without dropped tokens
  over a HELD share of the experts (`parallel/moe.py::dropless_moe`).
  The router reads the mixer's input or the MLP's (`cfg.router_input`);
  its rule is `cfg.routing`: the softmax over the chosen logits, or
  sigmoid scores with a selection bias, which is model state that the
  step moves after the loss (`stateful_loss`) and no gradient reaches.
- MLP `"dense"`: one gated MLP of width `d_dense`.

`cfg.activation` gates both MLP kinds; the head is the embedding's
transpose (`cfg.tied_head`) or a matrix of its own. Norms are
`ops.rmsnorm` (weight only).

Block parameters are stacked PER LEAF over the layers that have the
leaf, in layer order: a conv layer has no `wq`, a dense layer no
experts, and no zeros stand in for them. Where every layer has every
leaf the stacks are `[n_layers, ...]`. The leading layers are walked
one by one; `lax.scan` walks whole periods with the period's layers
unrolled inside its body, each taking its own row of each stack, so
every layer's kind is static and the depth costs one trace of a period.
Each block is rematerialised in the backward pass (`cfg.remat`). The
loss is taken in chunks of tokens, each chunk's logits recomputed in the
backward pass: at 16 k tokens over 38 k vocabulary rows the float32
logits alone would be 2.5 GB.

Parameters are fp32, compute is `cfg.dtype`; the router's product, its
scores, the selection bias, the head norms and every norm's statistics
are float32.
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
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.parallel.moe import (ACTIVATIONS, GMM_TILE, ROUTING,
                                  balance_bias, dropless_moe)

ATTENTION_KINDS = ("full", "window")
MIXER_KINDS = ATTENTION_KINDS + ("conv",)
MLP_KINDS = ("experts", "dense")
ROUTER_INPUTS = ("mixer", "mlp")

# which layers hold a leaf: those whose mixer or MLP is of its group
_GROUP = {"full": "attention", "window": "attention", "conv": "conv",
          "experts": "experts", "dense": "dense"}


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    attention: tuple[str, ...]        # one period, a MIXER kind per layer
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
    lead_attention: tuple[str, ...] = ()   # the layers before the periods
    lead_mlp: tuple[str, ...] = ()
    rotary: tuple[str, ...] = ("window",)  # attention kinds that turn q, k
    qk_norm: tuple[str, ...] = ()     # ... that norm each head of q, k first
    router_input: str = "mixer"       # the norm whose output the router reads
    routing: str = "softmax_topk"     # parallel/moe.py::ROUTING
    bias_rate: float = 1e-3           # a step of the selection bias
    activation: str = "relu"          # gates both MLP kinds
    d_dense: int = 0
    conv_taps: int = 3
    tied_head: bool = False

    def __post_init__(self):
        period, lead = len(self.attention), len(self.lead_attention)
        if len(self.mlp) != period or len(self.lead_mlp) != lead \
                or self.n_layers < lead or (self.n_layers - lead) % period:
            raise ValueError(
                f"{self.n_layers} layers are not {lead} leading layers "
                f"and whole periods of the pattern {self.attention} x "
                f"{self.mlp}")
        mixers = set(self.attention + self.lead_attention)
        if not (mixers <= set(MIXER_KINDS)
                and set(self.mlp + self.lead_mlp) <= set(MLP_KINDS)
                and set(self.rotary + self.qk_norm) <= set(ATTENTION_KINDS)):
            raise ValueError(
                f"layer kinds built so far: mixer {MIXER_KINDS} (rotary "
                f"and qk_norm list attention kinds: {ATTENTION_KINDS}), "
                f"mlp {MLP_KINDS}")
        if self.router_input not in ROUTER_INPUTS \
                or self.routing not in ROUTING \
                or self.activation not in ACTIVATIONS:
            raise ValueError(
                f"router_input is one of {ROUTER_INPUTS}, routing of "
                f"{ROUTING}, activation of {tuple(ACTIVATIONS)}")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"held {self.held} is no share of "
                             f"{self.n_experts} experts")

    @property
    def kinds(self) -> tuple[tuple[str, str], ...]:
        """(mixer, mlp) of every layer, top down."""
        lead = tuple(zip(self.lead_attention, self.lead_mlp))
        period = tuple(zip(self.attention, self.mlp))
        return lead + period * ((self.n_layers - len(lead)) // len(period))


# Tiny configuration for tests and rehearsals: the period of four, 7-to-1
# head grouping kept as 2 query heads a key/value head.
TINY = DecoderConfig(
    vocab_size=256, n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
    head_dim=16, attention=("full", "window", "window", "window"),
    mlp=("experts",) * 4, window=16, rope_theta=1.5e6, n_experts=8,
    top_k=3, d_expert=32, held=(0, 8), attn_block_q=16, attn_block_k=32,
    gmm_tile=8, loss_chunk=32)


def _leaves(cfg: DecoderConfig) -> dict:
    """name -> (group, shape of one layer's leaf, how it starts): every
    block leaf the configuration's kinds need. Group `"layer"`: every
    layer has it."""
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_expert
    count, groups = cfg.held[1], {_GROUP[k] for pair in cfg.kinds
                                  for k in pair}
    table = {"norm1": ("layer", (d,), "one"), "norm2": ("layer", (d,), "one")}
    if "attention" in groups:
        table.update(
            wq=("attention", (d, cfg.n_heads * hd), "normal"),
            wk=("attention", (d, cfg.n_kv_heads * hd), "normal"),
            wv=("attention", (d, cfg.n_kv_heads * hd), "normal"),
            wo=("attention", (cfg.n_heads * hd, d), "normal"))
        if cfg.qk_norm:
            table.update(q_norm=("attention", (hd,), "one"),
                         k_norm=("attention", (hd,), "one"))
    if "conv" in groups:
        table.update(conv_in=("conv", (d, 3 * d), "normal"),
                     conv_taps=("conv", (cfg.conv_taps, d), "taps"),
                     conv_out=("conv", (d, d), "normal"))
    if "experts" in groups:
        table.update(router=("experts", (d, cfg.n_experts), "normal"),
                     w_gate=("experts", (count, d, f), "normal"),
                     w_up=("experts", (count, d, f), "normal"),
                     w_down=("experts", (count, f, d), "normal"))
    if "dense" in groups:
        table.update(w1=("dense", (d, cfg.d_dense), "normal"),
                     w3=("dense", (d, cfg.d_dense), "normal"),
                     w2=("dense", (cfg.d_dense, d), "normal"))
    return table


def _layers_with(cfg: DecoderConfig, group: str, kinds=None) -> int:
    """How many of `kinds` (default: all the layers) hold the leaves of
    `group`."""
    kinds = cfg.kinds if kinds is None else kinds
    return sum(group == "layer" or group in (_GROUP[a], _GROUP[m])
               for a, m in kinds)


# The key a leaf is drawn from: one of `split(key, 12)`, the first ten
# in the order the first configuration drew them (its seeded weights
# are what its recorded losses were taken on), the later kinds' from
# splits of the eleventh, the selection bias from the twelfth.
_KEY_OF = {name: i for i, name in enumerate((
    "embed", "wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down",
    "head"))}
_LATER = ("conv_in", "conv_taps", "conv_out", "w1", "w3", "w2")


def init(key, cfg: DecoderConfig):
    """The parameter pytree: normal(0, init_std) matrices, norms at one,
    the convolution's taps uniform in +-1/sqrt(taps); a block leaf is
    stacked on axis 0 over the layers that have it, experts on axis 1
    (the held ones only)."""
    keys = list(jax.random.split(key, 12))
    later = dict(zip(_LATER, jax.random.split(keys[10], len(_LATER))))

    def draw(name, shape, how):
        if how == "one":
            return jnp.ones(shape)
        k = keys[_KEY_OF[name]] if name in _KEY_OF else later[name]
        if how == "taps":
            bound = cfg.conv_taps ** -0.5
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        return jax.random.normal(k, shape, jnp.float32) * cfg.init_std

    params = {
        "embed": draw("embed", (cfg.vocab_size, cfg.d_model), "normal"),
        "layers": {
            name: draw(name, (_layers_with(cfg, group), *shape), how)
            for name, (group, shape, how) in _leaves(cfg).items()},
        "norm_f": jnp.ones((cfg.d_model,)),
    }
    if not cfg.tied_head:
        params["head"] = draw("head", (cfg.d_model, cfg.vocab_size),
                              "normal")
    return params


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


def _head_norm(x, weight, eps: float):
    """RMSNorm over each head's dimensions. x: [B, T, H, hd]; float32
    statistics; plain jnp, fused by XLA with the rotary turn after it."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (xf * inv * weight).astype(x.dtype)


def _router(x, p):
    with jax.named_scope("router"):
        # in float32, whichever norm's output it reads
        return jnp.dot(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                       p["router"], precision=lax.Precision.HIGHEST)


def _layer(h, p, rope, *, cfg: DecoderConfig, attention: str, mlp: str):
    """One block. h: [B, T, D] in compute dtype; p: the layer's row of
    every leaf its kinds have (and `expert_bias`, where the routing has
    one) -> (h', the MoE layer's counts; None from a dense layer)."""
    b, t, d = h.shape
    hd = cfg.head_dim
    cast = functools.partial(jnp.asarray, dtype=h.dtype)
    x = rmsnorm(h, cast(p["norm1"]), cfg.rms_eps)
    if mlp == "experts" and cfg.router_input == "mixer":
        logits = _router(x, p)
    if attention == "conv":
        with jax.named_scope("mixer_conv"):
            y = short_conv(x @ cast(p["conv_in"]), p["conv_taps"])
            h = h + y @ cast(p["conv_out"])
    else:
        with jax.named_scope("attention_" + attention):
            q = (x @ cast(p["wq"])).reshape(b, t, cfg.n_heads, hd)
            k = (x @ cast(p["wk"])).reshape(b, t, cfg.n_kv_heads, hd)
            v = (x @ cast(p["wv"])).reshape(b, t, cfg.n_kv_heads, hd)
            if attention in cfg.qk_norm:
                q = _head_norm(q, p["q_norm"], cfg.rms_eps)
                k = _head_norm(k, p["k_norm"], cfg.rms_eps)
            if attention in cfg.rotary:
                q, k = _rope(q, *rope), _rope(k, *rope)
            a = flash_attention(
                q, k, v, True, None, cfg.attn_block_q, cfg.attn_block_k,
                cfg.window if attention == "window" else None)
            h = h + a.reshape(b, t, cfg.n_heads * hd) @ cast(p["wo"])
    y = rmsnorm(h, cast(p["norm2"]), cfg.rms_eps)
    if mlp == "dense":
        with jax.named_scope("mlp_dense"):
            act = ACTIVATIONS[cfg.activation](y @ cast(p["w1"]))
            return h + (act * (y @ cast(p["w3"]))) @ cast(p["w2"]), None
    if cfg.router_input == "mlp":
        logits = _router(y, p)
    m, counts = dropless_moe(
        y.reshape(b * t, d), logits, cast(p["w_gate"]), cast(p["w_up"]),
        cast(p["w_down"]), top_k=cfg.top_k, held=cfg.held,
        tile=cfg.gmm_tile, activation=cfg.activation,
        bias=p.get("expert_bias"))
    return h + m.reshape(b, t, d), counts


def hidden(params, tokens, cfg: DecoderConfig, bias=None):
    """tokens [B, T] -> (the last block's output [B, T, D], before the
    final norm; counts stacked over the MoE layers [layers, ...]).
    `bias`: the selection bias [MoE layers, n_experts], where the
    routing has one."""
    kinds, lead, period = cfg.kinds, len(cfg.lead_attention), \
        len(cfg.attention)
    h = params["embed"][tokens].astype(cfg.dtype)
    rope = rope_tables(tokens.shape[1], cfg)
    group_of = {name: group for name, (group, _, _) in _leaves(cfg).items()}
    layers = params["layers"]
    if bias is not None:
        layers = dict(layers, expert_bias=bias)
        group_of["expert_bias"] = "experts"

    def block(attention, mlp):
        fn = functools.partial(_layer, cfg=cfg, attention=attention, mlp=mlp)
        return jax.checkpoint(fn) if cfg.remat else fn

    def rows(stacks, at: int, before):
        """Layer `at`'s row of each leaf it has: its index in a leaf's
        stack is the number of layers in `before` that have the leaf."""
        mine = ("layer", *(_GROUP[k] for k in kinds[at]))
        return {name: stacks[name][_layers_with(cfg, group_of[name], before)]
                for name in sorted(stacks) if group_of[name] in mine}

    counts = []
    for at in range(lead):          # the leading layers, one by one
        h, c = block(*kinds[at])(h, rows(layers, at, kinds[:at]), rope)
        counts += [] if c is None else [jax.tree.map(lambda x: x[None], c)]
    blocks = [block(*pair) for pair in kinds[lead:lead + period]]

    def one_period(h, p):
        counts = []
        for j, fn in enumerate(blocks):
            h, c = fn(h, rows(p, lead + j, kinds[lead:lead + j]), rope)
            counts += [] if c is None else [c]
        return h, jax.tree.map(lambda *xs: jnp.stack(xs), *counts)

    def periods(name, per):
        """A leaf's stack past the leading layers' rows, by period."""
        x = layers[name]
        led = _layers_with(cfg, group_of[name], kinds[:lead])
        return (x[led:] if led else x).reshape(-1, per, *x.shape[1:])

    in_period = {name: _layers_with(cfg, group_of[name],
                                    kinds[lead:lead + period])
                 for name in sorted(layers)}
    h, scanned = lax.scan(one_period, h, {
        name: periods(name, per) for name, per in in_period.items() if per})
    scanned = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), scanned)
    if not counts:
        return h, scanned
    return h, jax.tree.map(lambda *xs: jnp.concatenate(xs), *counts, scanned)


def _head(params, cfg: DecoderConfig, dtype):
    return (params["embed"].T if cfg.tied_head
            else params["head"]).astype(dtype)


def apply(params, tokens, cfg: DecoderConfig, bias=None):
    """tokens [B, T] -> float32 logits [B, T, vocab] (whole: for tests
    and small sizes; the loss below never builds them at once)."""
    h, _ = hidden(params, tokens, cfg, bias)
    x = rmsnorm(h, params["norm_f"].astype(h.dtype), cfg.rms_eps)
    return jnp.dot(x, _head(params, cfg, x.dtype),
                   preferred_element_type=jnp.float32)


def loss_fn(params, tokens, cfg: DecoderConfig, bias=None):
    """Mean next-token cross-entropy over the B * (T - 1) positions that
    have a target -> (loss, counts). The mixers run at full T; the last
    position's logits are never formed."""
    b, t = tokens.shape
    h, counts = hidden(params, tokens, cfg, bias)
    x = rmsnorm(h, params["norm_f"].astype(h.dtype), cfg.rms_eps)
    with jax.named_scope("logits_loss"):
        x = x[:, :-1].reshape(b * (t - 1), -1)
        targets = tokens[:, 1:].reshape(b * (t - 1))
        head = _head(params, cfg, x.dtype)
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
# model state: counters that leave the step without a sync, and the
# selection bias
# ----------------------------------------------------------------------

def counters_init(cfg: DecoderConfig):
    """The model state of the operator's stateful form: `{"epoch_counters":
    {...}}`, scalars the step updates on the device, zeroed by the
    operator when an epoch starts and read once in `train.sync`, each
    onto that span under its key:

    `moe_assignments` (tokens x top_k x MoE layers x steps),
    `moe_assignments_held` (those that fell on a held expert),
    `moe_assignments_dropped` (held ones that found no row: 0),
    `moe_expert_tokens_max` / `_mean` (the most and the mean a held
    expert got in one layer of one step, over all of them),
    `moe_experts_held` / `_total`, `moe_steps`; with a selection bias
    (`state_init`) also `moe_assignments_bias_moved` (assignments whose
    expert the bias brought among the chosen) and `moe_bias_abs_max`
    (the largest bias after the step's move). The sums are float32
    (exact to 2**24, then to seven digits): int32 would wrap in an epoch
    of 2**31 / (tokens x top_k x layers) steps."""
    f32 = functools.partial(jnp.zeros, (), jnp.float32)
    i32 = functools.partial(jnp.zeros, (), jnp.int32)
    return {"epoch_counters": {
        "moe_assignments": f32(), "moe_assignments_held": f32(),
        "moe_assignments_dropped": f32(), "moe_expert_tokens_max": i32(),
        "moe_expert_tokens_mean": f32(), "moe_experts_held": i32(),
        "moe_experts_total": i32(), "moe_steps": i32()}}


def state_init(key, cfg: DecoderConfig):
    """`counters_init`, and where the routing has a selection bias:
    `expert_bias` [MoE layers, n_experts] float32, seeded normal(0,
    init_std) from the same key as the parameters (a checkpoint's biases
    are not zero; at zero the first step would not see the rule), and
    its two counters."""
    state = counters_init(cfg)
    if cfg.routing != "sigmoid_bias":
        return state
    f32 = functools.partial(jnp.zeros, (), jnp.float32)
    state["epoch_counters"].update(
        moe_assignments_bias_moved=f32(), moe_bias_abs_max=f32())
    state["expert_bias"] = cfg.init_std * jax.random.normal(
        jax.random.split(key, 12)[11],
        (_layers_with(cfg, "experts"), cfg.n_experts), jnp.float32)
    return state


def stateful_loss(params, state, tokens, cfg: DecoderConfig):
    """`loss_fn` in the operator's stateful form: the step's counts go
    into the state's running ones, and the selection bias, where there
    is one, makes its step after the loss (`parallel/moe.py::
    balance_bias`)."""
    bias = state.get("expert_bias")
    loss, counts = loss_fn(params, tokens, cfg, bias)
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
    names = [("moe_assignments", "assignments"),
             ("moe_assignments_held", "held"),
             ("moe_assignments_dropped", "dropped")]
    if bias is not None:
        names.append(("moe_assignments_bias_moved", "bias_moved"))
    for name, key in names:
        new[name] = old[name] + counts[key].sum().astype(jnp.float32)
    state = {**state, "epoch_counters": new}
    if bias is not None:
        bias = balance_bias(bias, counts["routed"], cfg.bias_rate)
        new["moe_bias_abs_max"] = jnp.abs(bias).max()
        state["expert_bias"] = bias
    return loss, state
