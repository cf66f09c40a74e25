"""A decoder assembled from a layer pattern.

A layer is a MIXER and an MLP, or one of them alone. The configuration
names, layer by layer, the kinds of the leading layers (`lead_attention`,
`lead_mlp`: a model's dense first layers, walked once) and of one period
(`attention`, `mlp`), which repeats down the rest of the depth (ROADMAP
D7). The kinds built so far:

- mixer `"full"`: causal attention; `"window"`: causal within a sliding
  window. Both with grouped-query heads, through `ops.flash_attention`
  (the kernel the GPT family runs). Rotary positions (rotate-half, on q
  and k, outside the kernel) and an RMSNorm over each head of q and k
  before them are properties of an attention kind: `cfg.rotary` and
  `cfg.qk_norm` list the kinds that have them (by default `"window"`
  turns and nothing is normed). `cfg.by_kind` gives each attention
  kind a HEAD COUNT and a ROTARY RULE of its own (`AttentionKind`;
  Laguna: 48 query heads on full layers and 64 under a window of 512,
  8 key/value heads of 128 on both): `wq`, `wo` and the gate's `wg`
  then cannot share a stack and are kept one a kind, `wq_full` /
  `wq_window` .. stacked over that kind's layers (`wk`, `wv` and the
  norms stay one stack over all); a kind turns the first `rope_dim`
  dimensions of a head (rotate-half within them) and the rest pass;
  its rates are `theta ** (-2i / rope_dim)`, or under YaRN (factor s,
  extending from L0 positions, beta_fast, beta_slow) `theta ** (-2i /
  d) ((1 - ramp_i) + ramp_i / s)` with `c(n) = d ln(L0 / (2 pi n)) /
  (2 ln theta)`, `low = max(floor(c(beta_fast)), 0)`, `high =
  min(ceil(c(beta_slow)), d - 1)`, `ramp_i = clip((i - low) / (high -
  low), 0, 1)`, and cos and sin times `rope_scale`; `_rope_for` makes
  a table a kind. `cfg.attn_gate` `"head"` adds a per-head output gate,
  `a_h <- sigmoid(x . w_g)_h a_h` before `wo`, reading the layer's
  normed input: a leaf `wg` `[d_model, heads]`, the product and the
  gate float32. `"element"` makes that gate ELEMENTWISE instead
  (Qwen3-Next): the query projection is doubled, `[query | gate] = x
  W_q` split a head (`wq` `[d_model, 2 heads head_dim]`, no leaf
  `wg`), and `a <- a * sigmoid(gate)`, a value a head DIMENSION (the
  sigmoid and the product float32; the `attn_gate_*` counters then
  count elements). A configuration that names no kind and no gate keeps
  its parameter tree, its seeded weights and its traced program.
- mixer `"conv"`: no attention at all. An input projection to three
  streams, the gated short convolution of `ops/short_conv.py` (B * x, a
  depthwise causal convolution of `conv_taps` taps, * C), an output
  projection. Its state is `conv_taps - 1` rows a sequence, not keys
  and values.
- mixer `"latent"`: causal attention over low-rank latents (MLA, as
  trained: nothing is absorbed). The query is two products with an
  RMSNorm of `q_lora_rank` between them — or, with `q_lora_rank` 0 (no
  query rank), ONE direct product (a leaf `wq_latent` `[d_model, heads
  x 192]`; no `wq_a`, `q_a_norm`, `wq_b`); ONE product gives the
  compressed keys-and-values (`kv_lora_rank`, normed) and a rotary key
  of `qk_rope_dim` that every head shares; the latent's up-projection
  gives each head a key part without positions (`qk_nope_dim`) and a
  value (`v_head_dim`). A head's query and key are `[nope | rope]`,
  `qk_nope_dim + qk_rope_dim` wide (192), its value and output
  `v_head_dim` (128): `ops.flash_attention` with two widths, scale
  1/sqrt(192). The rotary turn is on the rope part only, with
  INTERLEAVED pairing (dimensions 2i and 2i + 1 turn together: the
  kind's own, as rotate-half is the other kinds'); the shared key is
  turned once and broadcast over the heads. The kind turns unless
  `cfg.by_kind` names it with a turned width of 0 (Kimi Linear's
  `mla_use_nope`: the 64 shared dimensions then carry no position and
  no table is made for them).
- mixer `"ssm"`: a Mamba-2 state-space mixer (arXiv:2405.21060). ONE
  input projection to `[z | xBC | dt]` (`ssm_heads * ssm_head_dim` |
  that + 2 `ssm_groups * ssm_state` | `ssm_heads`; the leaf `ssm_in` is
  kept `[outputs, d_model]`: that sum is no multiple of the 128 lanes,
  and the device keeps a matrix whose minor dimension is not, after one
  that is, transposed whatever shape it is given); a depthwise causal
  convolution of `conv_taps` taps WITH bias and a SiLU over xBC (one
  operator, `ops/short_conv.py::mixer_conv`); `[x | B | C] = xBC`; `dt =
  softplus(dt + dt_bias)`, `A = -exp(A_log)`, the scan of
  `ops/ssd.py` in chunks of `ssm_chunk` (`S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T`, `y_t = S_t C_t + D x_t`, a `[head_dim, state]`
  float32 state a head, head h reading group `h // (heads / groups)`);
  an RMSNorm over each GROUP's channels of `y * silu(z)` — the gate
  before the norm — and an output projection. Its state is that
  matrix a head and `conv_taps - 1` rows, whatever the length.
- mixer `"delta"`: Gated DeltaNet linear attention (arXiv:2412.06464,
  as Qwen3-Next has it). ONE product `[q | k | v | z] = x W_qkvz`
  (`delta_in`: `delta_key_heads * delta_key_dim` twice, then
  `delta_value_heads * delta_value_dim` twice) and one `[b | a] = x
  W_ba` (`delta_ba`, kept `[outputs, d_model]` as `ssm_in` is: twice
  the value heads is no multiple of the 128 lanes); a depthwise causal
  convolution of `conv_taps` taps WITHOUT bias and a SiLU over `[q | k
  | v]`, q and k L2-normalised a head (`x * rsqrt(sum x^2 + 1e-6)`), q
  times `1 / sqrt(key dim)` (all of it `mixer_conv`, float32 inside and
  cast once); `beta = sigmoid(b)`, `g = -exp(A_log) * softplus(a +
  dt_bias)` a value head, float32; key
  head j serves the value heads `j r .. (j + 1) r - 1`, `r` = value
  heads / key heads; the gated delta rule of `ops/gated_delta.py` in
  chunks of 64 (`S' = exp(g_t) S_{t-1}`, `S_t = S' + k_t
  (beta_t (v_t - S'^T k_t))^T`, `o_t = S_t^T q_t`, a `[key dim, value
  dim]` float32 state a value head, from zero at position 0); then
  `RMSNorm(o) * w_n * silu(z)` a head — the norm BEFORE the gate, a
  plain weight `delta_norm` at one whatever `cfg.norm_plus_one` says,
  float32 — and an output projection `delta_out`. Its state is that
  matrix a head and `conv_taps - 1` rows, whatever the length.
- mixer `"kda"`: Kimi Delta Attention (arXiv:2510.26692), the delta
  rule with a decay a CHANNEL of the key. ONE product `[q | k | v] = x
  W` (`kda_in`: `delta_key_heads * delta_key_dim` twice, then `*
  delta_value_dim`; key and value heads are equal), a depthwise causal
  convolution of `conv_taps` taps WITHOUT bias and a SiLU over it, q
  and k L2-normalised a head, q times `1 / sqrt(key dim)` (`mixer_conv`,
  as the delta mixer's); ONE product
  down to two low ranks of the key dim each (`kda_down`, `[f | g]`),
  and from them up: `g = -exp(A_log)[h] * softplus(f W_f + dt_bias)` a
  head and key CHANNEL (`kda_f_up`, `kda_A_log` a head, `kda_dt_bias` a
  channel), float32, and the output gate `sigmoid(g W_g)` a value
  channel (`kda_g_up`); `beta = sigmoid(x W_beta)` a head (`kda_beta`,
  kept `[heads, d_model]` as `delta_ba` is); the rule of `ops/kda.py` in
  chunks of 64 (`S' = Diag(exp(g_t)) S_{t-1}`, then the delta rule's
  write and read; a `[key dim, value dim]` float32 state a head, from
  zero at position 0); then `RMSNorm(o) * w_n * sigmoid(gate)` a head —
  the norm BEFORE the gate, a plain weight `kda_norm` at one — and an
  output projection `kda_out`. Its parts run under the scopes
  `kda_projections`, `kda_conv`, `kda_rule`, `kda_gate_norm` inside
  `mixer_kda`. Its state is that matrix a head and `conv_taps - 1`
  rows, whatever the length.
- mixer `"none"` / MLP `"none"`: the layer is the other part alone — a
  model whose blocks are each a mixer OR a feed-forward part with one
  norm reads as such layers. It holds no leaf of the absent side (one
  norm, not two), and no zeros stand in for it. A router that reads the
  mixer's norm needs a mixer.
- MLP `"experts"`: top-k routed experts without dropped tokens
  over a HELD share of the experts (`parallel/moe.py::dropless_moe`).
  The router reads the mixer's input or the MLP's (`cfg.router_input`);
  its rule is `cfg.routing`: the softmax over the chosen logits, or
  sigmoid scores with a selection bias, which is model state that the
  step moves after the loss (`stateful_loss`) and no gradient reaches.
  `cfg.routed_scale` multiplies the routing weights; `cfg.d_shared` > 0
  adds a SHARED expert of that width, one gated MLP every token takes,
  beside the routed sum and outside the grouped matmul's rows;
  `cfg.shared_gate` puts a gate on it, `sigmoid(y . w_s)` a token (a
  leaf `ws_token_gate` `[d_model]`, the product and the gate float32).
- MLP `"dense"`: one MLP of width `d_dense`. A pattern WITHOUT an
  `"experts"` layer (a dense model through and through) names none of
  `n_experts`, `top_k`, `d_expert`, `held`, holds no router or expert
  leaf, makes none of the `moe_*` counters and counts no routing.

`cfg.activation` is the MLP kinds' (and the shared expert's) activation;
they are gated, `W_down (act(W_gate y) * (W_up y))`, unless `cfg.gated`
is false: `W_down act(W_up y)`, two matrices and no gate leaf
(`"relu2"`, the squared ReLU, is such a model's; its routed experts'
`w_up` is `[held, d_expert, d_model]`, rows as `w_down`'s). The head is the embedding's
transpose (`cfg.tied_head`) or a matrix of its own, `[d_model, vocab]`
or, with `cfg.head_rows`, `[vocab, d_model]` as the embedding is: a
vocabulary slice that is no multiple of the 128 lanes is then nobody's
minor dimension (the device keeps a leaf whose minor dimension is not,
after one that is, transposed whatever shape it is given, and the step
copies it and its moments back and forth: PERF.md section 6, PR 39).
Norms are `ops.rmsnorm` (weight only); under `cfg.norm_plus_one` they
have the form `x / sqrt(mean(x^2) + eps) * (1 + w)` (the two a layer,
the final one, q's and k's head norms; `_gain` adds the one in float32
before any cast) and their `w` starts seeded normal(0, init_std), not at
one. `cfg.sandwich` gives every layer
two more (`norm1_post`, `norm2_post`): the mixer's output and the MLP's
are each normed BEFORE the residual sum, `h + RMSNorm(part(RMSNorm(h)))`.

`cfg.mtp` = 1 adds a multi-token-prediction block after the last layer
(`params["mtp"]`): position i's last hidden state (before the final
norm) and the embedding of token i + 1, each normed, concatenated in
that order and projected 2 D -> D, go through one more block of the
last layer's kinds (weights, router and selection bias of its own) and
a final norm of its own to the SAME head; it predicts token i + 2, and
the loss is `CE_main + cfg.mtp_weight * CE_mtp`, each a mean over its
own valid positions. The embedding and the head get both uses'
gradients. The block's routing counts and its bias row come last among
the MoE layers'.

Block parameters are stacked PER LEAF over the layers that have the
leaf, in layer order: a conv layer has no `wq`, a dense layer no
experts, and no zeros stand in for them. Where every layer has every
leaf the stacks are `[n_layers, ...]`. The leading layers are walked
one by one; `lax.scan` walks whole periods with the period's layers
unrolled inside its body, each taking its own row of each stack, so
every layer's kind is static and the depth costs one trace of a period.
Each block is rematerialised in the backward pass (`cfg.remat`; with
`"parts"` a block's mixer and its MLP each under a checkpoint of its
own, so that the step never holds both parts' residuals: the delta
mixer's float32 convolution output and entering states beside the
expert block's static rows were the peak of the Qwen3-Next step). A
rematerialised block keeps its input and nothing it computed but what
`_kept_across_remat` names for its mixer: under `cfg.index_topk` the
gradient that `index_kl`'s one pass made beside the loss (below). The
loss is taken in chunks of tokens, each chunk's logits recomputed in the
backward pass: at 16 k tokens over 38 k vocabulary rows the float32
logits alone would be 2.5 GB.

`cfg.diffusion_block` = b > 0 trains the model by BLOCK DIFFUSION
instead (SDAR, arXiv:2510.06303; the training pass is BD3-LM's,
arXiv:2503.09573): the objective is a property of the configuration, as
the layer kinds are. A sequence of L tokens is cut into blocks of b;
each block draws a masking rate, its tokens are masked at that rate,
and ONE pass over `[x_0 ; x_t]` — the clean sequence, then its noised
copy: 2 L rows, both halves at positions 0 .. L - 1 — predicts every
masked token from its block's noised tokens, seen both ways, and the
CLEAN tokens of the blocks before it (`ops.flash_attention`'s
`diffusion` mask; mixers `full` and `none` only, no MTP block). The
final norm, the head and the chunked loss run over the noised half
alone; there is no shift: row i predicts token i. The noise is drawn
INSIDE the step (`diffusion_inputs`), from `fold_in(key(noise_seed),
noise_step)`: two int32 of model state beside the counters — the seed
drawn once from the key the weights are drawn from, the step moved by
`stateful_loss` and, not being an epoch counter, never reset — so a
snapshot carries where the noise stands. With `diffusion_block` 0 none
of this is traced and a configuration's program is what it was.

`cfg.loops` = T > 1 makes the model a LOOPED one (Ouro,
arXiv:2510.25741): the whole stack of layers is walked T times a
forward pass with ONE set of weights, the final norm after every walk,
its output the next walk's input and the head's (nothing is re-injected
between walks). `lax.scan` over the walks goes AROUND the scan over the
periods and the weights are constants of both: a period is still traced
once, the step holds one compute-dtype copy of the weight stacks, and
the backward pass adds each walk's gradient stack into ONE float32
stack a leaf (the sum over the walks is the gradient of a shared
weight); each block is rematerialised as without loops, so a block
input is kept a layer and walk. `cfg.exit_gate` adds a gate
(`params["exit_gate"]`: a weight `[d_model]` and a bias, one for all
walks, float32) that reads every walk's normed output: `lam_t =
sigmoid(w . x_t + b)`, survival `S_t = prod over j <= t of (1 -
lam_j)`, the exit distribution `p(t) = lam_t S_(t-1)`, the last walk
taking what is left; and the loss becomes the expected-exit loss,
`sum over t of p(t) CE_t - cfg.exit_beta H(p)` a token, T passes of the
chunked head in one scan over the walks with a weight a token that
carries its gradient to the gate (`loop_loss`). Built for layers that
count nothing (no `experts`, no `ssm`), without an MTP block or block
diffusion. With `loops` 1, no sandwich and no gate none of this is
traced and a configuration's program is what it was.

`cfg.index_topk` = k > 0 makes the `full` layers' attention LEARNED
SPARSE (DeepSeek Sparse Attention's lightning indexer, as Keye-VL-2.0's
language model has it): a property of the `full` kind, as `attn_gate`
and `by_kind` are. Beside q, k, v the layer's normed input x, read as a
CONSTANT, gives an indexer — `q_I = x W_qI` as `index_heads` heads of
`index_dim`, ONE key head `k_I = LayerNorm(x W_kI)` (weight and bias:
the leaf `index_k_norm`'s two rows), both turned rotate-half over their
whole width, `w = (x W_w) index_heads ** -1/2 index_dim ** -1/2` float32
(leaves `w_index_q`, `w_index_k`, `index_k_norm`, `w_index_w`, stacked
over the `full` layers) — whose scores `I[t, s] = sum_j w[t, j] relu(q_I
[t, j] . k_I[s])` choose, a query, its `min(t + 1, k)` best keys at or
before it (`ops/sparse_index.py::index_select`: ties to the lower index,
no gradient through the choice; scope `index_select`). The attention
runs over those alone, one selection for every head
(`ops.flash_attention(selected=)`), and the indexer learns from the
attention it steered: `L_I`, the mean over rows and layers of the KL
divergence of the heads' averaged probabilities (a constant) from the
softmax of I over the selection (`index_kl`, scope `index_loss`).
`index_kl` makes the loss and its gradient to q_I, k_I, w in ONE pass
and its backward rule only scales the gradient, so a rematerialised
block KEEPS those three arrays (`ops.sparse_index.KL_SAVED_ACROSS_REMAT`
through `save_only_these_names`: at 16 384 tokens `[T, 16, 64]` bf16 +
`[T, 64]` + `[T, 16]` = 35 MiB a layer, stacked by the layer scan): the
recomputed copy of the pass then has no reader and is dead code, and the
pass runs once a layer and step instead of twice. The attention's output
and log-sum-exp are NOT kept: `flash_fwd` and `index_scores` run in both
passes. The
loss is `CE + cfg.index_loss_weight L_I`; by the two stop-gradients the
indexer's four leaves get `L_I`'s gradient alone and every other leaf
the cross-entropy's alone; `counts` gains `loss_main` and `loss_index`.
One algorithm, adapted by what it observes: for T <= k every causal key
is selected, no plane is built and the plain causal kernel runs (the
indexer's loss stays, over every causal key). The indexer's products are
in `cfg.index_dtype` (None: `cfg.dtype`) with float32 sums; the ReLU, w,
the sum over heads, the threshold and the loss float32. Not built beside
an MTP block, block diffusion, a stack walked more than once or
`by_kind`. With `index_topk` 0 none of this is traced and a
configuration's program is what it was.

Parameters are fp32, compute is `cfg.dtype`; the router's product, its
scores, the selection bias, the head norms, the exit gate, the
attention's output gate, the shared expert's gate, the delta rules'
(`delta`, `kda`) decay, write strength, L2 norms, state and gated norm,
and every norm's statistics are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import (diffusion_tiles, flash_attention,
                                   forward_tiles, window_scores)
from ray_tpu.ops.gated_delta import (CHUNK as DELTA_CHUNK, gated_delta,
                                     paired_heads)
from ray_tpu.ops.kda import kda
from ray_tpu.ops.layernorm import rmsnorm
from ray_tpu.ops.short_conv import mixer_conv, short_conv
from ray_tpu.ops.sparse_index import (KL_SAVED_ACROSS_REMAT, index_kl,
                                      index_select)
from ray_tpu.ops.ssd import CHUNK as SSD_CHUNK, ssd
from ray_tpu.parallel.moe import (ACTIVATIONS, GMM_TILE, ROUTING,
                                  balance_bias, dropless_moe, static_rows)

ATTENTION_KINDS = ("full", "window")
MIXER_KINDS = ATTENTION_KINDS + ("conv", "latent", "ssm", "delta", "none",
                                 "kda")
RECURRENT_KINDS = ("ssm", "delta", "kda")   # carry a state, count a decay
MLP_KINDS = ("experts", "dense", "none")
ROUTER_INPUTS = ("mixer", "mlp")
ATTN_GATES = ("", "head", "element")

# which layers hold a leaf: those whose mixer or MLP is of its group;
# group "layer": every layer; groups "mixer" and "mlp" (the two norms'
# where some layer is one part alone): those that have that part
_GROUP = {"full": "attention", "window": "attention", "conv": "conv",
          "latent": "latent", "ssm": "ssm", "delta": "delta", "kda": "kda",
          "experts": "experts",
          "dense": "dense"}


def _groups_of(pair) -> tuple[str, ...]:
    """The leaf groups a layer of kinds (mixer, mlp) holds; an attention
    layer also the group of its own kind (`"full"`, `"window"`: the
    leaves whose shape follows a kind's head count, `cfg.by_kind`)."""
    return ("layer",) + tuple(
        g for part, kind in zip(("mixer", "mlp"), pair) if kind != "none"
        for g in (part, _GROUP[kind]) + (kind,) * (kind in ATTENTION_KINDS))


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What an attention kind has of its own where a configuration names
    its kinds (`DecoderConfig.by_kind`): the query heads, and the rotary
    rule — theta, the TURNED width (the first `rope_dim` dimensions of a
    head turn, rotate-half within them, the rest pass; 0: none turn),
    YaRN's blend of interpolated and extrapolated rates, a factor on cos
    and sin."""
    n_heads: int
    rope_theta: float = 1e4
    rope_dim: int = 0
    yarn: tuple[float, int, float, float] | None = None   # factor, the
    #                                   positions it extends from,
    #                                   beta_fast, beta_slow
    rope_scale: float = 1.0           # on cos and sin (YaRN's
    #                                   attention_factor)


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
    n_experts: int = 0                # the router's outputs: ALL experts
    top_k: int = 0
    d_expert: int = 0
    held: tuple[int, int] = (0, 0)    # (first, count): the experts held
    #                                   here; a pattern without an
    #                                   `experts` layer needs none of the four
    rms_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool | str = True          # a block under one checkpoint;
    #                                   "parts": its mixer and its MLP each
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
    activation: str = "relu"          # of both MLP kinds
    gated: bool = True                # False: W_down act(W_up y), no gate
    d_dense: int = 0
    conv_taps: int = 3
    tied_head: bool = False
    q_lora_rank: int = 0              # the latent mixer's ranks (0: no query
    #                                   rank, one direct product) and widths
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0              # a head's key part without positions
    qk_rope_dim: int = 0              # the rotary part; ONE key for all heads
    v_head_dim: int = 0
    d_shared: int = 0                 # a shared expert beside the routed
    routed_scale: float = 1.0         # a factor on the routing weights
    mtp: int = 0                      # multi-token-prediction blocks: 0 or 1
    mtp_weight: float = 0.3           # lambda on the second loss term
    ssm_heads: int = 0                # the ssm mixer: heads of ssm_head_dim,
    ssm_head_dim: int = 0             # ... ssm_groups pairs of B and C of
    ssm_groups: int = 1               # ... ssm_state; conv_taps taps
    ssm_state: int = 0
    ssm_chunk: int = SSD_CHUNK        # positions a chunk of the scan holds
    ssm_dt_range: tuple[float, float, float] = (1e-3, 0.1, 1e-4)  # min, max,
    #                                   floor of dt at initialisation
    count_rows: bool = False          # the counters moe_rows_static /
    #                                   _filled / _walked (with an MTP
    #                                   block: always)
    diffusion_block: int = 0          # > 0: trained by block diffusion over
    #                                   blocks of this many tokens
    head_rows: bool = False           # the untied head kept [vocab, d_model]
    loops: int = 1                    # walks of the whole stack a forward
    #                                   pass, one set of weights
    sandwich: bool = False            # an RMSNorm AFTER the mixer and after
    #                                   the MLP too, before each residual sum
    exit_gate: bool = False           # a gate after every walk, and the
    #                                   expected-exit loss over the walks
    exit_beta: float = 0.0            # ... minus this times the entropy of
    #                                   the exit distribution
    by_kind: tuple[tuple[str, AttentionKind], ...] = ()   # head count and
    #                                   rotary rule by attention kind; then
    #                                   n_heads, rope_theta and rotary are
    #                                   not read for those layers; "latent"
    #                                   with rope_dim 0: it turns nothing
    attn_gate: str = ""               # a sigmoid gate on the attention's
    #                                   output, before wo: "head" (a leaf
    #                                   `wg`, a value a head) or "element"
    #                                   (the second half of each head of a
    #                                   doubled query projection)
    norm_plus_one: bool = False       # norms of the form (1 + w): the two a
    #                                   layer, the final one, q's and k's
    shared_gate: bool = False         # sigmoid(x . w) a token on the shared
    #                                   expert's output
    delta_key_heads: int = 0          # the delta mixer: key heads of
    delta_value_heads: int = 0        # ... delta_key_dim, value heads of
    delta_key_dim: int = 0            # ... delta_value_dim (a multiple of
    delta_value_dim: int = 0          # ... the key heads); conv_taps taps;
    #                                   the kda mixer reads the same four
    #                                   (as many value heads as key heads)
    index_topk: int = 0               # > 0: the `full` layers' attention runs
    #                                   over the keys a learned indexer
    #                                   selects, this many a query
    index_heads: int = 0              # the indexer's query heads of
    index_dim: int = 0                # ... index_dim; ONE key head
    index_loss_weight: float = 1.0    # on the indexer's loss beside the CE
    index_dtype: Any = None           # of the indexer's products (None: dtype)

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
                f"and qk_norm list attention kinds: {ATTENTION_KINDS}; "
                f"the latent mixer turns its rope part unless by_kind "
                f"names it with rope_dim 0), "
                f"mlp {MLP_KINDS}; \"none\" on one side only")
        if any(pair == ("none", "none") or (
                pair == ("none", "experts") and self.router_input == "mixer")
               for pair in self.kinds):
            raise ValueError(
                "a layer is a mixer, an MLP or both: (none, none) is no "
                "layer, and the router of (none, experts) reads the MLP's "
                "norm (router_input \"mlp\")")
        ssm = (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
               self.ssm_state)
        if "ssm" in mixers and (min(ssm) < 1
                                or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                "the ssm mixer needs ssm_heads (a multiple of ssm_groups), "
                f"ssm_head_dim, ssm_groups and ssm_state: got {ssm}")
        latent = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_dim,
                  self.qk_rope_dim, self.v_head_dim)
        if "latent" in mixers and (min(latent[1:]) < 1 or latent[0] < 0
                                   or self.qk_rope_dim % 2):
            raise ValueError(
                "the latent mixer needs kv_lora_rank, qk_nope_dim, "
                "qk_rope_dim (even) and v_head_dim, and q_lora_rank or 0 "
                "for no query rank (one direct product, a leaf "
                f"`wq_latent`): got {latent}")
        if self.diffusion_block and (
                self.diffusion_block < 0 or self.mtp
                or not mixers <= {"full", "none"}):
            raise ValueError(
                "block diffusion (diffusion_block > 0) is built for the "
                "mixers \"full\" and \"none\" and without an MTP block: a "
                "window, a convolution or a scan would run across the "
                "[clean ; noised] halves")
        if self.mtp not in (0, 1) or self.d_shared < 0:
            raise ValueError(
                f"mtp is 0 or 1 block (got {self.mtp}), d_shared the "
                f"shared expert's width or 0 (got {self.d_shared})")
        if self.router_input not in ROUTER_INPUTS \
                or self.routing not in ROUTING \
                or self.activation not in ACTIVATIONS:
            raise ValueError(
                f"router_input is one of {ROUTER_INPUTS}, routing of "
                f"{ROUTING}, activation of {tuple(ACTIVATIONS)}")
        first, count = self.held
        if self.moe_layers and (first < 0 or count < 1
                                or first + count > self.n_experts):
            raise ValueError(
                f"held {self.held} is no share of {self.n_experts} experts: "
                "a pattern with an \"experts\" layer names the router's "
                "outputs (n_experts), top_k, d_expert and the (first, "
                "count) it holds; a dense pattern (no \"experts\" layer) "
                "leaves all four out")
        if "kda" in mixers and (
                min(self.delta_key_heads, self.delta_key_dim,
                    self.delta_value_dim) < 1
                or self.delta_value_heads != self.delta_key_heads):
            raise ValueError(
                "the kda mixer needs delta_key_heads, as many "
                "delta_value_heads, delta_key_dim and delta_value_dim: got "
                f"{self.delta_key_heads}, {self.delta_value_heads}, "
                f"{self.delta_key_dim}, {self.delta_value_dim}")
        delta = (self.delta_key_heads, self.delta_value_heads,
                 self.delta_key_dim, self.delta_value_dim)
        if "delta" in mixers and (
                min(delta) < 1
                or self.delta_value_heads % self.delta_key_heads):
            raise ValueError(
                "the delta mixer needs delta_key_heads, delta_value_heads "
                "(a multiple of them), delta_key_dim and delta_value_dim: "
                f"got {delta}")
        if isinstance(self.attn_gate, bool):    # PR 55's spelling
            object.__setattr__(self, "attn_gate",
                               "head" if self.attn_gate else "")
        if self.attn_gate not in ATTN_GATES:
            raise ValueError(
                f"attn_gate is one of {ATTN_GATES}: got {self.attn_gate!r}")
        if self.shared_gate and not self.d_shared:
            raise ValueError(
                "shared_gate is a gate on the shared expert (d_shared)")
        if self.remat not in (True, False, "parts") or (
                self.remat == "parts" and self.router_input == "mixer"
                and "experts" in self.mlp + self.lead_mlp):
            raise ValueError(
                'remat is True (a block under one checkpoint), False or '
                '"parts" (its mixer and its MLP each; the router then '
                f'reads the MLP\'s norm): got {self.remat!r}')
        if self.mtp and (self.norm_plus_one or self.shared_gate
                         or mixers & {"delta", "kda"}):
            raise ValueError(
                "the MTP block is not built beside norm_plus_one, "
                "shared_gate or the delta and kda mixers")
        if self.loops < 1 or (self.loops > 1 and (
                self.moe_layers or mixers & set(RECURRENT_KINDS) or self.mtp
                or self.diffusion_block)):
            raise ValueError(
                f"loops is the walks of the stack, 1 or more (got "
                f"{self.loops}); a stack walked more than once is built for "
                "layers that count nothing (no \"experts\" MLP, no "
                f"mixer of {RECURRENT_KINDS}), without an MTP block or "
                "block diffusion")
        if self.exit_gate and self.loops < 2:
            raise ValueError(
                "exit_gate (the exit distribution over the walks) needs "
                "loops > 1")
        if self.sandwich and self.d_shared:
            raise ValueError(
                "the sandwich norm reads ONE output of the MLP: not built "
                "beside a shared expert (d_shared)")
        named = tuple(kind for kind, _ in self.by_kind)
        rules = dict(self.by_kind)
        if self.by_kind and (
                len(set(named)) != len(named)
                or set(named) - {"latent"} != mixers & set(ATTENTION_KINDS)
                or ("latent" in mixers) != ("latent" in rules)
                or ("latent" in rules and (
                    rules["latent"].rope_dim
                    or rules["latent"].n_heads != self.n_heads))
                or any(rule.n_heads % self.n_kv_heads or rule.rope_dim % 2
                       or not 0 <= rule.rope_dim <= self.head_dim
                       for _, rule in self.by_kind)):
            raise ValueError(
                f"by_kind names each attention kind of the pattern once "
                f"(got {named} for {sorted(mixers & set(ATTENTION_KINDS))})"
                f", its heads a multiple of the {self.n_kv_heads} key/value "
                f"heads, its turned width even and at most a head's "
                f"{self.head_dim}; beside the latent mixer it names "
                "\"latent\" too, with n_heads and rope_dim 0: a latent "
                "mixer under by_kind turns nothing (without by_kind it "
                "turns its rope part)")
        if self.index_topk and (
                self.index_topk < 0 or min(self.index_heads,
                                           self.index_dim) < 1
                or self.index_dim % 2 or "full" not in mixers or self.mtp
                or self.diffusion_block or self.loops > 1 or self.by_kind
                or "full" not in self.rotary):
            raise ValueError(
                "index_topk (learned sparse attention on the \"full\" "
                "layers) needs index_heads and an even index_dim, a "
                "\"full\" layer that turns (rotary), and is not built "
                "beside an MTP block, block diffusion, a stack walked more "
                "than once or by_kind")
        if self.attn_gate and (self.mtp or self.loops > 1
                               or not mixers & set(ATTENTION_KINDS)):
            raise ValueError(
                "attn_gate gates the heads of the mixers \"full\" and "
                "\"window\" and counts how open it is: not built for an "
                "MTP block or a stack walked more than once")

    @property
    def kinds(self) -> tuple[tuple[str, str], ...]:
        """(mixer, mlp) of every layer, top down."""
        lead = tuple(zip(self.lead_attention, self.lead_mlp))
        period = tuple(zip(self.attention, self.mlp))
        return lead + period * ((self.n_layers - len(lead)) // len(period))

    def heads_of(self, kind: str) -> int:
        """The query heads of an attention layer of `kind`."""
        return dict(self.by_kind)[kind].n_heads if self.by_kind \
            else self.n_heads

    def leaf_of(self, leaf: str, kind: str) -> str:
        """The name of a leaf whose shape follows the head count (`wq`,
        `wo`, the gate's `wg`) on a layer of attention `kind`: one stack
        for all attention layers, or under `by_kind` one a kind."""
        return f"{leaf}_{kind}" if self.by_kind else leaf

    @property
    def moe_layers(self) -> int:
        """The layers that route, the MTP block's last: the rows of the
        selection bias and of the stacked counts."""
        kinds = self.kinds
        return sum(m == "experts" for _, m in kinds + kinds[-1:] * self.mtp)


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
    layer has it — the two norms, unless some layer is a mixer or an
    MLP alone: then a norm's group is `"mixer"` or `"mlp"`, the layers
    that have that part; `cfg.sandwich` adds `norm1_post` and
    `norm2_post` with the same groups. An ungated configuration has no
    gate leaf (`w_gate`, `ws_gate`, `w1`)."""
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_expert
    count, groups = cfg.held[1], {g for pair in cfg.kinds
                                  for g in _groups_of(pair)}
    alone = any("none" in pair for pair in cfg.kinds)
    # a norm of the form (1 + w) starts near zero, seeded: at zero the
    # comparison with the reference would not see the form
    one = "centred" if cfg.norm_plus_one else "one"
    table = {"norm1": ("mixer" if alone else "layer", (d,), one),
             "norm2": ("mlp" if alone else "layer", (d,), one)}
    if cfg.sandwich:    # the norms after the mixer and after the MLP
        table.update(norm1_post=table["norm1"], norm2_post=table["norm2"])
    if "attention" in groups:
        table.update(
            wk=("attention", (d, cfg.n_kv_heads * hd), "normal"),
            wv=("attention", (d, cfg.n_kv_heads * hd), "normal"))
        # the leaves a head count shapes: one stack over the attention
        # layers, or one a kind over that kind's layers
        for group in ATTENTION_KINDS if cfg.by_kind else ("attention",):
            if group not in groups:
                continue
            heads = cfg.heads_of(group)
            # the elementwise gate is the second half of each head of wq
            wide = 2 if cfg.attn_gate == "element" else 1
            table.update({
                cfg.leaf_of("wq", group): (group, (d, wide * heads * hd),
                                           "normal"),
                cfg.leaf_of("wo", group): (group, (heads * hd, d), "normal")})
            if cfg.attn_gate == "head":
                table[cfg.leaf_of("wg", group)] = (group, (d, heads),
                                                   "normal")
        if cfg.qk_norm:
            table.update(q_norm=("attention", (hd,), one),
                         k_norm=("attention", (hd,), one))
        if cfg.index_topk:  # the indexer of the `full` layers
            table.update(
                w_index_q=("full", (d, cfg.index_heads * cfg.index_dim),
                           "normal"),
                w_index_k=("full", (d, cfg.index_dim), "normal"),
                # its key's LayerNorm: the weight's row, then the bias's
                index_k_norm=("full", (2, cfg.index_dim), "norm_bias"),
                w_index_w=("full", (d, cfg.index_heads), "normal"))
    if "latent" in groups:
        h, rope = cfg.n_heads, cfg.qk_rope_dim
        if cfg.q_lora_rank:
            table.update(
                wq_a=("latent", (d, cfg.q_lora_rank), "normal"),
                q_a_norm=("latent", (cfg.q_lora_rank,), "one"),
                wq_b=("latent", (cfg.q_lora_rank,
                                 h * (cfg.qk_nope_dim + rope)), "normal"))
        else:   # no query rank: one direct product
            table["wq_latent"] = (
                "latent", (d, h * (cfg.qk_nope_dim + rope)), "normal")
        table.update(
            wkv_a=("latent", (d, cfg.kv_lora_rank + rope), "normal"),
            kv_a_norm=("latent", (cfg.kv_lora_rank,), "one"),
            wkv_b=("latent", (cfg.kv_lora_rank,
                              h * (cfg.qk_nope_dim + cfg.v_head_dim)),
                   "normal"),
            wo_latent=("latent", (h * cfg.v_head_dim, d), "normal"))
    if "conv" in groups:
        table.update(conv_in=("conv", (d, 3 * d), "normal"),
                     conv_taps=("conv", (cfg.conv_taps, d), "taps"),
                     conv_out=("conv", (d, d), "normal"))
    if "ssm" in groups:
        inner = cfg.ssm_heads * cfg.ssm_head_dim
        conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
        table.update(
            ssm_in=("ssm", (inner + conv + cfg.ssm_heads, d), "normal"),
            ssm_conv=("ssm", (cfg.conv_taps, conv), "taps"),
            ssm_conv_bias=("ssm", (conv,), "taps"),
            A_log=("ssm", (cfg.ssm_heads,), "A_log"),
            D=("ssm", (cfg.ssm_heads,), "one"),
            dt_bias=("ssm", (cfg.ssm_heads,), "dt_bias"),
            ssm_norm=("ssm", (inner,), "one"),
            ssm_out=("ssm", (inner, d), "normal"))
    if "delta" in groups:
        keys = cfg.delta_key_heads * cfg.delta_key_dim
        values = cfg.delta_value_heads * cfg.delta_value_dim
        table.update(
            # [q | k | v | z]; [b | a] kept [outputs, d_model] as ssm_in
            # is: twice the value heads is no multiple of the 128 lanes
            delta_in=("delta", (d, 2 * keys + 2 * values), "normal"),
            delta_ba=("delta", (2 * cfg.delta_value_heads, d), "normal"),
            delta_conv=("delta", (cfg.conv_taps, 2 * keys + values), "taps"),
            delta_A_log=("delta", (cfg.delta_value_heads,), "delta_A_log"),
            delta_dt_bias=("delta", (cfg.delta_value_heads,), "one"),
            delta_norm=("delta", (cfg.delta_value_dim,), "one"),
            delta_out=("delta", (values, d), "normal"))
    if "kda" in groups:
        heads, rank = cfg.delta_key_heads, cfg.delta_key_dim
        keys, values = heads * cfg.delta_key_dim, heads * cfg.delta_value_dim
        table.update(
            kda_in=("kda", (d, 2 * keys + values), "normal"),   # [q | k | v]
            kda_conv=("kda", (cfg.conv_taps, 2 * keys + values), "taps"),
            # down to the two low ranks, [decay | gate]; beta kept
            # [heads, d_model] as delta_ba is
            kda_down=("kda", (d, 2 * rank), "normal"),
            kda_f_up=("kda", (rank, keys), "normal"),
            kda_g_up=("kda", (rank, values), "normal"),
            kda_beta=("kda", (heads, d), "normal"),
            kda_A_log=("kda", (heads,), "A_log"),
            kda_dt_bias=("kda", (keys,), "dt_bias"),
            kda_norm=("kda", (cfg.delta_value_dim,), "one"),
            kda_out=("kda", (values, d), "normal"))
    if "experts" in groups:
        table.update(router=("experts", (d, cfg.n_experts), "normal"),
                     w_gate=("experts", (count, d, f), "normal"),
                     w_up=("experts", (count, d, f), "normal"),
                     w_down=("experts", (count, f, d), "normal"))
        if cfg.d_shared:
            table.update(
                ws_gate=("experts", (d, cfg.d_shared), "normal"),
                ws_up=("experts", (d, cfg.d_shared), "normal"),
                ws_down=("experts", (cfg.d_shared, d), "normal"))
            if cfg.shared_gate:
                table["ws_token_gate"] = ("experts", (d,), "normal")
    if "dense" in groups:
        table.update(w1=("dense", (d, cfg.d_dense), "normal"),
                     w3=("dense", (d, cfg.d_dense), "normal"),
                     w2=("dense", (cfg.d_dense, d), "normal"))
    if not cfg.gated:
        for gate in ("w_gate", "ws_gate", "w1"):
            table.pop(gate, None)
        if "experts" in groups:     # `dropless_moe`: rows are hidden units
            table["w_up"] = ("experts", (count, f, d), "normal")
    return table


def _layers_with(cfg: DecoderConfig, group: str, kinds=None) -> int:
    """How many of `kinds` (default: all the layers) hold the leaves of
    `group`."""
    kinds = cfg.kinds if kinds is None else kinds
    return sum(group in _groups_of(pair) for pair in kinds)


# The key a leaf is drawn from: one of `split(key, 12)`, the first ten
# in the order the first configuration drew them (its seeded weights
# are what its recorded losses were taken on), the later kinds' from
# splits of the eleventh, the selection bias from the twelfth. The
# kinds after those fold their place in `_NEWER` into the eleventh (a
# longer `_LATER` would move the second configuration's weights; a name
# appended to `_NEWER` moves nobody's), the MTP block's leaves theirs
# into `fold_in(eleventh, _MTP_KEY)`.
_KEY_OF = {name: i for i, name in enumerate((
    "embed", "wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down",
    "head"))}
_LATER = ("conv_in", "conv_taps", "conv_out", "w1", "w3", "w2")
_NEWER = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo_latent", "ws_gate", "ws_up",
          "ws_down", "proj", "ssm_in", "ssm_conv", "ssm_conv_bias", "A_log",
          "dt_bias", "ssm_out", "exit_gate", "wg", "wq_full", "wo_full",
          "wg_full", "wq_window", "wo_window", "wg_window", "delta_in",
          "delta_ba", "delta_conv", "delta_A_log", "delta_out",
          "ws_token_gate", "norm1", "norm2", "q_norm", "k_norm", "norm_f",
          "norm1_post", "norm2_post", "wq_latent", "kda_in", "kda_conv",
          "kda_down", "kda_f_up", "kda_g_up", "kda_beta", "kda_A_log",
          "kda_dt_bias", "kda_out", "w_index_q", "w_index_k", "w_index_w")
_MTP_KEY = 1 << 16
_NOISE_KEY = 1 << 17    # folded into the init key: the noise's seed


def _mtp_leaves(cfg: DecoderConfig) -> dict:
    """The MTP block's own leaves: those of one layer of the last
    layer's kinds."""
    mine = _groups_of(cfg.kinds[-1])
    return {name: spec for name, spec in _leaves(cfg).items()
            if spec[0] in mine}


def init(key, cfg: DecoderConfig):
    """The parameter pytree: normal(0, init_std) matrices, norms at one,
    the convolutions' taps (and the ssm mixer's convolution bias)
    uniform in +-1/sqrt(taps); the ssm mixer's `A_log` the log of a
    uniform draw in [1, 16], its `dt_bias` the inverse softplus of a
    log-uniform draw in `cfg.ssm_dt_range` (Mamba-2's own start: a head
    forgets over 1 / (dt A), between a handful and a thousand
    positions); a block leaf is stacked on axis 0 over the layers that
    have it, experts on axis 1 (the held ones only); the exit gate's
    weight normal(0, init_std) as a matrix is, its bias zero; the delta
    mixer's `delta_A_log` the log of a draw in (0, 16], its
    `delta_dt_bias` and `delta_norm` one; the kda mixer's `kda_A_log`
    and `kda_dt_bias` drawn as the ssm mixer's are (A in [1, 16] a head,
    softplus(dt_bias) log-uniform in `cfg.ssm_dt_range` a channel: a
    head's channels forget over a handful to thousands of positions),
    its `kda_norm` one; under `cfg.norm_plus_one` the (1 + w) norms' w
    normal(0, init_std)."""
    keys = list(jax.random.split(key, 12))
    later = dict(zip(_LATER, jax.random.split(keys[10], len(_LATER))))

    def draw(name, shape, how, mtp=False):
        if how == "one":
            return jnp.ones(shape)
        if how == "norm_bias":      # [..., (weight, bias), width]
            return jnp.zeros(shape).at[..., 0, :].set(1.0)
        if mtp:   # by the leaf's place among all the names there are
            k = jax.random.fold_in(
                jax.random.fold_in(keys[10], _MTP_KEY),
                sorted(_leaves(cfg) | {"proj": ()}).index(name))
        elif name in _NEWER:
            k = jax.random.fold_in(keys[10], _NEWER.index(name))
        else:
            k = keys[_KEY_OF[name]] if name in _KEY_OF else later[name]
        if how == "taps":
            bound = cfg.conv_taps ** -0.5
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        if how == "A_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1, 16))
        if how == "delta_A_log":    # A in (0, 16]
            return jnp.log(16.0 - jax.random.uniform(k, shape, jnp.float32,
                                                     0, 16))
        if how == "dt_bias":
            low, high, floor = cfg.ssm_dt_range
            dt = jnp.maximum(floor, jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(low), jnp.log(high))))
            return dt + jnp.log(-jnp.expm1(-dt))
        return jax.random.normal(k, shape, jnp.float32) * cfg.init_std

    params = {
        "embed": draw("embed", (cfg.vocab_size, cfg.d_model), "normal"),
        "layers": {
            name: draw(name, (_layers_with(cfg, group), *shape), how)
            for name, (group, shape, how) in _leaves(cfg).items()},
        "norm_f": draw("norm_f", (cfg.d_model,),
                       "centred" if cfg.norm_plus_one else "one"),
    }
    if not cfg.tied_head:
        shape = (cfg.d_model, cfg.vocab_size)
        params["head"] = draw(
            "head", shape[::-1] if cfg.head_rows else shape, "normal")
    if cfg.exit_gate:   # Linear(d_model, 1) with bias, one for all walks
        params["exit_gate"] = {
            "w": draw("exit_gate", (cfg.d_model,), "normal"),
            "b": jnp.zeros(())}
    if cfg.mtp:
        d = cfg.d_model
        params["mtp"] = {
            "proj": draw("proj", (2 * d, d), "normal", mtp=True),
            "norm_h": jnp.ones((d,)), "norm_e": jnp.ones((d,)),
            "norm_f": jnp.ones((d,)),
            "layer": {name: draw(name, shape, how, mtp=True)
                      for name, (_, shape, how) in _mtp_leaves(cfg).items()}}
    return params


def rope_tables(positions, cfg: DecoderConfig, dim: int | None = None):
    """cos, sin [T, dim / 2] of position * theta ** (-2i / dim), float32,
    for the T `positions` given (float32: a row's place in ITS sequence,
    which under block diffusion is not its row); `dim` the turned width:
    a head's (the default) or the latent mixer's rope part."""
    return _turned(_rope_rates(cfg, dim), positions)


def _rope_rates(cfg: DecoderConfig, dim: int | None = None):
    """theta ** (-2i / dim), [dim / 2]: what a unit of position turns
    each pair by."""
    half = (cfg.head_dim if dim is None else dim) // 2
    return cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def _kind_rates(rule: AttentionKind):
    """What a unit of position turns each pair of a kind's turned width
    d = `rule.rope_dim` by, [d / 2]: theta ** (-2i / d), or under YaRN
    (Hugging Face's `_compute_yarn_parameters`, `truncate` on) its blend
    with the interpolated rate. With `c(n) = d ln(L0 / (2 pi n)) / (2 ln
    theta)` — the pair that turns n times over the L0 positions the
    scaling extends from — `low = max(floor(c(beta_fast)), 0)`, `high =
    min(ceil(c(beta_slow)), d - 1)`, `ramp_i = clip((i - low) / (high -
    low), 0, 1)`: `theta ** (-2i / d) * ((1 - ramp_i) + ramp_i /
    factor)`. The pairs that turn fast keep their rate, the slow ones
    turn `factor` times slower."""
    half = rule.rope_dim // 2
    i = jnp.arange(half, dtype=jnp.float32)
    rates = rule.rope_theta ** (-i / half)
    if rule.yarn is None:
        return rates
    factor, original, fast, slow = rule.yarn

    def pair_turning(n):
        return rule.rope_dim * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(rule.rope_theta))

    low = max(math.floor(pair_turning(fast)), 0)
    high = min(math.ceil(pair_turning(slow)), rule.rope_dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return rates * (1.0 - ramp) + rates / factor * ramp


def _turned(rates, positions):
    angle = positions[:, None] * rates[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, cos, sin):
    """Rotate-half pairing: dimension i turns with dimension i + half.
    x: [B, T, H, hd]; computed in float32, returned in x's dtype. Tables
    narrower than half a head turn the head's first 2 x their width and
    the rest passes as it is."""
    if 2 * cos.shape[-1] < x.shape[-1]:
        width = 2 * cos.shape[-1]
        return jnp.concatenate(
            [_rope(x[..., :width], cos, sin), x[..., width:]], axis=-1)
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


def _gain(w, cfg: "DecoderConfig"):
    """A norm's weight as it multiplies: the leaf, or under
    `cfg.norm_plus_one` one plus the leaf (float32, before any cast)."""
    return 1.0 + w if cfg.norm_plus_one else w


def _deinterleave(w, lead: int, dim: int):
    """The columns of a projection whose every `lead + dim` outputs end
    in a rope part of `dim`, that part reordered (0, 2, 4, .. | 1, 3,
    5, ..): rotate-half on the product then turns the pairs (2i, 2i + 1)
    of the original order, and a score, a sum over a query's and a
    key's rope parts alike, does not see the reordering. w: [in, heads *
    (lead + dim)]; the reordering is of the weight, once a pass, not of
    the tokens."""
    w3 = w.reshape(w.shape[0], -1, lead + dim)
    turned = w3[:, :, lead:].reshape(*w3.shape[:2], dim // 2, 2)
    return jnp.concatenate(
        [w3[:, :, :lead], turned.swapaxes(2, 3).reshape(*w3.shape[:2], dim)],
        axis=-1).reshape(w.shape)


def _latent_attention(x, p, rope, cfg: DecoderConfig):
    """The latent mixer on the first norm's output x [B, T, D] -> the
    mixer's part of the residual [B, T, D]. `rope`: the table its rope
    part turns by, or None where it turns nothing."""
    b, t, _ = x.shape
    h, nope, rot = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    r_kv, dv = cfg.kv_lora_rank, cfg.v_head_dim
    cast = functools.partial(jnp.asarray, dtype=x.dtype)

    def paired(w, lead):    # a weight's rope columns, where they turn
        return _deinterleave(cast(w), lead, rot) if rope else cast(w)

    # no query rank: one direct product
    wq = paired(p["wq_b" if cfg.q_lora_rank else "wq_latent"], nope)
    wkv_a = paired(p["wkv_a"], r_kv)
    c_q = rmsnorm(x @ cast(p["wq_a"]), cast(p["q_a_norm"]), cfg.rms_eps) \
        if cfg.q_lora_rank else x
    q = (c_q @ wq).reshape(b, t, h, nope + rot)
    kv_a = x @ wkv_a                       # [c_kv | the shared rotary key]
    c_kv = rmsnorm(kv_a[..., :r_kv], cast(p["kv_a_norm"]), cfg.rms_eps)
    # the up-projection's columns are a head's [k_nope | v]: two
    # products, so that neither part is cut out of a 256-wide array
    wkv_b = cast(p["wkv_b"]).reshape(r_kv, h, nope + dv)
    k_nope = (c_kv @ wkv_b[:, :, :nope].reshape(r_kv, h * nope)).reshape(
        b, t, h, nope)
    v = (c_kv @ wkv_b[:, :, nope:].reshape(r_kv, h * dv)).reshape(
        b, t, h, dv)
    k_rope = kv_a[..., r_kv:].reshape(b, t, 1, rot)
    if rope:
        k_rope = _rope(k_rope, *rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], *rope)], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, h, rot))], -1)
    # 192-wide q and k, 128-wide v and o; scale 1 / sqrt(192)
    a = flash_attention(q, k, v, True, None, cfg.attn_block_q,
                        cfg.attn_block_k, None)
    return a.reshape(b, t, h * dv) @ cast(p["wo_latent"])


def _ssm_mixer(x, p, cfg: DecoderConfig):
    """The state-space mixer on the first norm's output x [B, T, D] ->
    (its part of the residual [B, T, D], {the most negative sum of dt A
    over a chunk, the largest dt}: what the counters keep)."""
    b, t, _ = x.shape
    h, hp, gn = cfg.ssm_heads, cfg.ssm_head_dim, \
        cfg.ssm_groups * cfg.ssm_state
    inner = h * hp
    cast = functools.partial(jnp.asarray, dtype=x.dtype)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    zxbcdt = x @ cast(p["ssm_in"]).T
    z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * gn]
    with jax.named_scope("ssm_conv"):   # the convolution, a bias, SiLU
        xbc = mixer_conv(xbc, p["ssm_conv"], p["ssm_conv_bias"])
    dt = jax.nn.softplus(f32(zxbcdt[..., 2 * inner + 2 * gn:])
                         + p["dt_bias"])                   # [B, T, H]
    a = -jnp.exp(p["A_log"])
    y = ssd(xbc[..., :inner].reshape(b, t, h, hp), dt, a,
            xbc[..., inner:inner + gn].reshape(b, t, cfg.ssm_groups, -1),
            xbc[..., inner + gn:].reshape(b, t, cfg.ssm_groups, -1),
            p["D"], cfg.ssm_chunk)
    # the gate BEFORE the norm; statistics over each group's channels
    gated = (f32(y).reshape(b, t, inner) * jax.nn.silu(f32(z))).reshape(
        b, t, cfg.ssm_groups, -1)
    normed = _head_norm(gated, p["ssm_norm"].reshape(cfg.ssm_groups, -1),
                        cfg.rms_eps).reshape(b, t, inner).astype(x.dtype)
    stats = lax.stop_gradient({
        "ssm_log_decay_min": (dt * a).reshape(
            b, t // cfg.ssm_chunk, cfg.ssm_chunk, h).sum(2).min(),
        "ssm_dt_max": dt.max()})
    return normed @ cast(p["ssm_out"]), stats


def _delta_mixer(x, p, cfg: DecoderConfig):
    """The gated-delta-rule mixer on the first norm's output x [B, T, D]
    -> (its part of the residual [B, T, D], {the least sum of the log
    decay over a chunk, the write strengths summed}: what the counters
    keep)."""
    b, t, _ = x.shape
    g_heads, h = cfg.delta_key_heads, cfg.delta_value_heads
    dk, dv = cfg.delta_key_dim, cfg.delta_value_dim
    keys, values = g_heads * dk, h * dv
    cast = functools.partial(jnp.asarray, dtype=x.dtype)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    qkvz = x @ cast(p["delta_in"])
    ba = jnp.dot(x, cast(p["delta_ba"]).T,
                 preferred_element_type=jnp.float32)           # [B, T, 2 H]
    # the convolution over [q | k | v], no bias, SiLU; q and k
    # L2-normalised a head, q times dk ** -0.5
    with jax.named_scope("delta_conv"):
        qkv = mixer_conv(qkvz[..., :2 * keys + values], p["delta_conv"],
                         None, 2 * keys, dk)
    beta = jax.nn.sigmoid(ba[..., :h])
    g = -jnp.exp(p["delta_A_log"]) * jax.nn.softplus(
        ba[..., h:] + p["delta_dt_bias"])
    o = gated_delta(qkv[..., :keys].reshape(b, t, g_heads, dk),
                    qkv[..., keys:2 * keys].reshape(b, t, g_heads, dk),
                    qkv[..., 2 * keys:].reshape(b, t, h, dv), g, beta)
    # the norm BEFORE the gate, a plain weight; statistics over a head
    z = f32(qkvz[..., 2 * keys + values:]).reshape(b, t, h, dv)
    gated = (_head_norm(f32(o), p["delta_norm"], cfg.rms_eps)
             * jax.nn.silu(z)).reshape(b, t, values).astype(x.dtype)
    stats = lax.stop_gradient({
        "delta_log_decay_min": g.reshape(
            b, t // DELTA_CHUNK, DELTA_CHUNK, h).sum(2).min(),
        "delta_beta_sum": beta.sum()})
    return gated @ cast(p["delta_out"]), stats


def _kda_mixer(x, p, cfg: DecoderConfig):
    """The Kimi-Delta-Attention mixer on the first norm's output x [B,
    T, D] -> (its part of the residual [B, T, D], {the least sum of the
    log decay over a chunk, a chunk's sum's spread over a head's
    channels summed, the write strengths and the output gates summed}:
    what the counters keep)."""
    b, t, _ = x.shape
    h, dk, dv = cfg.delta_key_heads, cfg.delta_key_dim, cfg.delta_value_dim
    keys, values = h * dk, h * dv
    cast = functools.partial(jnp.asarray, dtype=x.dtype)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    with jax.named_scope("kda_projections"):
        qkv = x @ cast(p["kda_in"])
        low = x @ cast(p["kda_down"])               # [decay | gate] ranks
        decay_in = jnp.dot(low[..., :dk], cast(p["kda_f_up"]),
                           preferred_element_type=jnp.float32)
        gate_in = jnp.dot(low[..., dk:], cast(p["kda_g_up"]),
                          preferred_element_type=jnp.float32)
        beta = jax.nn.sigmoid(jnp.dot(x, cast(p["kda_beta"]).T,
                                      preferred_element_type=jnp.float32))
        # a head's A times a channel's softplus, float32, <= 0
        g = -jnp.exp(p["kda_A_log"])[:, None] * jax.nn.softplus(
            decay_in + p["kda_dt_bias"]).reshape(b, t, h, dk)
    with jax.named_scope("kda_conv"):   # over [q | k | v], no bias, SiLU;
        # q and k L2-normalised a head, q times dk ** -0.5
        qkv = mixer_conv(qkv, p["kda_conv"], None, 2 * keys, dk)
        q = qkv[..., :keys].reshape(b, t, h, dk)
        k = qkv[..., keys:2 * keys].reshape(b, t, h, dk)
        v = qkv[..., 2 * keys:].reshape(b, t, h, dv)
    with jax.named_scope("kda_rule"):
        o = kda(q, k, v, g, beta)
    with jax.named_scope("kda_gate_norm"):
        # the norm BEFORE the gate, a plain weight; statistics over a head
        gate = jax.nn.sigmoid(gate_in).reshape(b, t, h, dv)
        gated = (_head_norm(f32(o), p["kda_norm"], cfg.rms_eps)
                 * gate).reshape(b, t, values).astype(x.dtype)
    total = g.reshape(b, t // DELTA_CHUNK, DELTA_CHUNK, h, dk).sum(2)
    stats = lax.stop_gradient({
        "kda_log_decay_min": total.min(),
        "kda_decay_spread_sum": (total.max(-1) - total.min(-1)).sum(),
        "kda_beta_sum": beta.sum(), "kda_gate_sum": gate.sum()})
    return gated @ cast(p["kda_out"]), stats


def _indexer(x, p, cfg: DecoderConfig):
    """The indexer's inputs from the first norm's output x [B, T, D],
    which it reads as a constant: (q_I [B, T, H_I, D_I] and k_I [B, T,
    D_I] in `cfg.index_dtype`, both turned rotate-half over their whole
    width at the rates of `cfg.rope_theta`; w [B, T, H_I] float32, times
    H_I ** -0.5 D_I ** -0.5). k_I is ONE head under a LayerNorm with
    weight and bias (`index_k_norm`'s two rows), float32 statistics."""
    b, t, _ = x.shape
    heads, dim = cfg.index_heads, cfg.index_dim
    dtype = cfg.index_dtype or cfg.dtype
    x = lax.stop_gradient(x).astype(dtype)
    cast = functools.partial(jnp.asarray, dtype=dtype)
    q = (x @ cast(p["w_index_q"])).reshape(b, t, heads, dim)
    k = jnp.dot(x, cast(p["w_index_k"]), preferred_element_type=jnp.float32)
    mean = k.mean(-1, keepdims=True)
    var = ((k - mean) ** 2).mean(-1, keepdims=True)
    k = ((k - mean) * lax.rsqrt(var + cfg.rms_eps) * p["index_k_norm"][0]
         + p["index_k_norm"][1]).astype(dtype)
    table = rope_tables(jnp.arange(t, dtype=jnp.float32), cfg, dim)
    w = jnp.dot(x, cast(p["w_index_w"]), preferred_element_type=jnp.float32)
    return (_rope(q, *table), _rope(k[:, :, None], *table)[:, :, 0],
            w * (heads ** -0.5 * dim ** -0.5))


def _mlp(y, p, cfg: DecoderConfig, up: str, down: str, gate: str):
    """W_down (act(W_gate y) * (W_up y)), or W_down act(W_up y) where
    the configuration is ungated."""
    cast = functools.partial(jnp.asarray, dtype=y.dtype)
    act = ACTIVATIONS[cfg.activation]
    if not cfg.gated:
        return act(y @ cast(p[up])) @ cast(p[down])
    return (act(y @ cast(p[gate])) * (y @ cast(p[up]))) @ cast(p[down])


def _router(x, p):
    with jax.named_scope("router"):
        # in float32, whichever norm's output it reads
        return jnp.dot(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                       p["router"], precision=lax.Precision.HIGHEST)


def _layer(h, p, rope, *, cfg: DecoderConfig, attention: str, mlp: str):
    """One block. h: [B, T, D] in compute dtype; p: the layer's row of
    every leaf its kinds have (and `expert_bias`, where the routing has
    one) -> (h', what the layer counted beside it, one flat dict: the
    expert layer's counts where it has experts, the scan's statistics
    where its mixer is one, both or neither)."""
    b, t, d = h.shape
    hd = cfg.head_dim
    cast = functools.partial(jnp.asarray, dtype=h.dtype)
    gain = functools.partial(_gain, cfg=cfg)
    found = {}

    def joined(h, y, post: str):
        """The residual sum; under `cfg.sandwich` of the part's output
        normed by the layer's `post` leaf."""
        if cfg.sandwich:
            y = rmsnorm(y, cast(gain(p[post])), cfg.rms_eps)
        return h + y

    if attention != "none":
        x = rmsnorm(h, cast(gain(p["norm1"])), cfg.rms_eps)
    if mlp == "experts" and cfg.router_input == "mixer":
        logits = _router(x, p)
    if attention == "ssm":
        with jax.named_scope("mixer_ssm"):
            y, stats = _ssm_mixer(x, p, cfg)
            h, found = joined(h, y, "norm1_post"), {**found, **stats}
    elif attention == "delta":
        with jax.named_scope("mixer_delta"):
            y, stats = _delta_mixer(x, p, cfg)
            h, found = joined(h, y, "norm1_post"), {**found, **stats}
    elif attention == "kda":
        with jax.named_scope("mixer_kda"):
            y, stats = _kda_mixer(x, p, cfg)
            h, found = joined(h, y, "norm1_post"), {**found, **stats}
    elif attention == "conv":
        with jax.named_scope("mixer_conv"):
            y = short_conv(x @ cast(p["conv_in"]), p["conv_taps"])
            h = joined(h, y @ cast(p["conv_out"]), "norm1_post")
    elif attention == "latent":
        with jax.named_scope("attention_latent"):
            table = rope.get("latent") if cfg.by_kind else rope
            h = joined(h, _latent_attention(x, p, table, cfg), "norm1_post")
    elif attention != "none":
        with jax.named_scope("attention_" + attention):
            heads = cfg.heads_of(attention)
            leaf = functools.partial(cfg.leaf_of, kind=attention)
            q = x @ cast(p[leaf("wq")])
            if cfg.attn_gate == "element":  # a head's [query | gate]
                q = q.reshape(b, t, heads, 2 * hd)
                q, gate_in = q[..., :hd], q[..., hd:]
            else:
                q = q.reshape(b, t, heads, hd)
            k = (x @ cast(p["wk"])).reshape(b, t, cfg.n_kv_heads, hd)
            v = (x @ cast(p["wv"])).reshape(b, t, cfg.n_kv_heads, hd)
            if attention in cfg.qk_norm:
                q = _head_norm(q, gain(p["q_norm"]), cfg.rms_eps)
                k = _head_norm(k, gain(p["k_norm"]), cfg.rms_eps)
            if cfg.by_kind:     # the kind's own table, where it turns
                table = rope.get(attention)
            else:
                table = rope if attention in cfg.rotary else None
            if table is not None:
                q, k = _rope(q, *table), _rope(k, *table)
            selected = plane = lse = lse_i = None
            if cfg.index_topk and attention == "full":
                with jax.named_scope("index_scores"):
                    index = _indexer(x, p, cfg)
                if t > cfg.index_topk:   # else every causal key is selected
                    with jax.named_scope("index_select"):
                        plane, lse_i, tiles, beyond = index_select(
                            *index, cfg.index_topk,
                            (cfg.attn_block_q, cfg.attn_block_k))
                    selected = (plane, tiles)
                    found.update(
                        index_pairs_selected=tiles.sum().astype(jnp.float32),
                        index_pairs_beyond_window=beyond.sum().astype(
                            jnp.float32),
                        index_tiles_visited=(tiles > 0).sum().astype(
                            jnp.float32))
            a = flash_attention(
                q, k, v, True, None, cfg.attn_block_q, cfg.attn_block_k,
                cfg.window if attention == "window" else None,
                cfg.diffusion_block or None, selected)
            if selected:    # and the rows' log-sum-exp over the selection
                a, lse = a
            if cfg.index_topk and attention == "full":
                with jax.named_scope("index_loss"):
                    # the indexer against the attention it steered, the
                    # rows' sum: the one count a gradient passes through
                    found["index_kl_sum"] = index_kl(
                        *index, *lax.stop_gradient((q, k)), plane, lse,
                        lse_i, hd ** -0.5)
            if cfg.attn_gate == "element":
                # a <- a o sigmoid(gate), a value a head dimension
                gate = jax.nn.sigmoid(gate_in.astype(jnp.float32))
                found["attn_gate_sum_" + attention] = lax.stop_gradient(
                    gate.sum())
                a = (a.astype(jnp.float32) * gate).astype(a.dtype)
            elif cfg.attn_gate == "head":
                # a_h <- sigmoid(x . w_g)_h a_h: float32, as the router is
                gate = jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.float32), p[leaf("wg")],
                    precision=lax.Precision.HIGHEST))
                found["attn_gate_sum_" + attention] = lax.stop_gradient(
                    gate.sum())
                a = (a.astype(jnp.float32) * gate[..., None]).astype(a.dtype)
            h = joined(h, a.reshape(b, t, heads * hd) @ cast(p[leaf("wo")]),
                       "norm1_post")
    if mlp != "none":
        y = rmsnorm(h, cast(gain(p["norm2"])), cfg.rms_eps)
    if mlp == "dense":
        with jax.named_scope("mlp_dense"):
            h = joined(h, _mlp(y, p, cfg, "w3", "w2", "w1"), "norm2_post")
    elif mlp == "experts":
        if cfg.router_input == "mlp":
            logits = _router(y, p)
        m, counts = dropless_moe(
            y.reshape(b * t, d), logits,
            cast(p["w_gate"]) if cfg.gated else None, cast(p["w_up"]),
            cast(p["w_down"]), top_k=cfg.top_k, held=cfg.held,
            tile=cfg.gmm_tile, activation=cfg.activation,
            bias=p.get("expert_bias"), scale=cfg.routed_scale)
        h = joined(h, m.reshape(b, t, d), "norm2_post")
        found = {**found, **counts}
        if cfg.d_shared:
            with jax.named_scope("mlp_shared"):
                # what every chip of the deployment computes alike:
                # every token, one plain MLP, no row of the grouped matmul
                shared = _mlp(y, p, cfg, "ws_up", "ws_down", "ws_gate")
                if cfg.shared_gate:
                    # sigmoid(y . w) a token: float32, as the router is
                    gate = jax.nn.sigmoid(jnp.dot(
                        y.astype(jnp.float32), p["ws_token_gate"],
                        precision=lax.Precision.HIGHEST))
                    found["shared_gate_sum"] = lax.stop_gradient(gate.sum())
                    shared = (shared.astype(jnp.float32)
                              * gate[..., None]).astype(shared.dtype)
                h = h + shared
    return h, found


def _rope_for(t: int, cfg: DecoderConfig):
    """The one rotary table a configuration's mixers turn the t rows
    by: the latent mixer's rope part, or a head; under `cfg.by_kind`
    `{kind: table}` of the kinds that turn, each by its own rule
    (`_kind_rates`, cos and sin times the rule's `rope_scale`). Row i
    stands at position i; under block diffusion the rows are two copies
    of t / 2 positions and both halves turn alike."""
    latent = "latent" in cfg.attention + cfg.lead_attention
    # the rates before the positions: the order the recorded programs
    # of the configurations that turn were traced in
    if cfg.by_kind:
        rates = {kind: _kind_rates(rule) for kind, rule in cfg.by_kind
                 if rule.rope_dim}
    else:
        rates = _rope_rates(cfg, cfg.qk_rope_dim if latent else None)
    if cfg.diffusion_block:
        positions = jnp.tile(jnp.arange(t // 2, dtype=jnp.float32), 2)
    else:
        positions = jnp.arange(t, dtype=jnp.float32)
    if not cfg.by_kind:
        return _turned(rates, positions)
    scale = {kind: rule.rope_scale for kind, rule in cfg.by_kind}
    return {kind: tuple(x if scale[kind] == 1.0 else x * scale[kind]
                        for x in _turned(rates[kind], positions))
            for kind in rates}


def _kept_across_remat(cfg: DecoderConfig, attention: str) -> tuple:
    """The names a rematerialised block with this mixer keeps: the
    gradient to q_I, k_I and w that `index_kl`'s one pass made beside
    the loss, where the block has an indexer's loss, so that the
    recomputed copy of the pass is dead code; else none. The
    attention's own two names are not among them: `flash_fwd` runs in
    both passes."""
    if cfg.index_topk and attention == "full":
        return KL_SAVED_ACROSS_REMAT
    return ()


def _rematerialised(fn, cfg: DecoderConfig, attention: str):
    """`fn` under `jax.checkpoint`; with no name to keep no policy is
    passed, and the block traces as it always has."""
    names = _kept_across_remat(cfg, attention)
    if not names:
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*names))


def _block(cfg: DecoderConfig, attention: str, mlp: str):
    fn = functools.partial(_layer, cfg=cfg, attention=attention, mlp=mlp)
    if cfg.remat != "parts" or "none" in (attention, mlp):
        return _rematerialised(fn, cfg, attention) if cfg.remat else fn
    # the mixer and the MLP each under a checkpoint of its own: one
    # part's residuals are gone before the other's backward makes its own
    mixer = _rematerialised(functools.partial(
        _layer, cfg=cfg, attention=attention, mlp="none"), cfg, attention)
    rest = jax.checkpoint(functools.partial(
        _layer, cfg=cfg, attention="none", mlp=mlp))

    def parts(h, p, rope):
        h, counted = mixer(h, p, rope)
        h, more = rest(h, p, rope)
        return h, {**counted, **more}

    return parts


def hidden(params, tokens, cfg: DecoderConfig, bias=None):
    """tokens [B, T] -> (the last block's output [B, T, D], before the
    final norm; counts stacked over the MoE layers [layers, ...]).
    `bias`: the selection bias [MoE layers, n_experts], where the
    routing has one (the MTP block's row, the last, is not read here).
    With an ssm mixer the counts also hold `ssm_log_decay_min` and
    `ssm_dt_max`, scalars over all its layers; with a delta mixer
    `delta_log_decay_min` (a scalar) and `delta_beta_sum` a layer; with
    a kda mixer `kda_log_decay_min` (a scalar) and `kda_decay_spread_sum`,
    `kda_beta_sum`, `kda_gate_sum` a layer; under `cfg.index_topk`
    `index_kl_sum` a layer (the rows' sum of the indexer's KL: the one
    count a gradient passes through) and, where a selection is made,
    `index_pairs_selected`, `index_pairs_beyond_window`,
    `index_tiles_visited` a layer.

    With `cfg.loops` = T > 1 the same layers are walked T times, the
    final norm after EVERY walk, its output the next walk's input and
    the head's: -> (every walk's NORMED output [T, B, T_seq, D], no
    counts: such layers count nothing)."""
    kinds, lead, period = cfg.kinds, len(cfg.lead_attention), \
        len(cfg.attention)
    h = params["embed"][tokens].astype(cfg.dtype)
    rope = _rope_for(tokens.shape[1], cfg)
    group_of = {name: group for name, (group, _, _) in _leaves(cfg).items()}
    layers = params["layers"]
    if bias is not None:
        layers = dict(layers, expert_bias=bias[:-1] if cfg.mtp else bias)
        group_of["expert_bias"] = "experts"

    def rows(stacks, at: int, before):
        """Layer `at`'s row of each leaf it has: its index in a leaf's
        stack is the number of layers in `before` that have the leaf."""
        mine = _groups_of(kinds[at])
        return {name: stacks[name][_layers_with(cfg, group_of[name], before)]
                for name in sorted(stacks) if group_of[name] in mine}

    def run(fn, h, p, into):
        """What a block counted beside h, each key onto its own list."""
        h, found = fn(h, p, rope)
        for key, x in found.items():
            into.setdefault(key, []).append(x)
        return h

    def stack(h):
        """One walk of the layers, top down -> (h, counts)."""
        led = {}
        for at in range(lead):          # the leading layers, one by one
            h = run(_block(cfg, *kinds[at]), h,
                    rows(layers, at, kinds[:at]), led)
        led = {key: [x[None] for x in led[key]] for key in sorted(led)}
        blocks = [_block(cfg, *pair) for pair in kinds[lead:lead + period]]

        def one_period(h, p):
            into = {}
            for j, fn in enumerate(blocks):
                h = run(fn, h, rows(p, lead + j, kinds[lead:lead + j]), into)
            return h, {key: jnp.stack(into[key]) for key in sorted(into)}

        def periods(name, per):
            """A leaf's stack past the leading layers' rows, by period."""
            x = layers[name]
            led = _layers_with(cfg, group_of[name], kinds[:lead])
            return (x[led:] if led else x).reshape(-1, per, *x.shape[1:])

        in_period = {name: _layers_with(cfg, group_of[name],
                                        kinds[lead:lead + period])
                     for name in sorted(layers)}
        h, scanned = lax.scan(one_period, h, {
            name: periods(name, per) for name, per in in_period.items()
            if per})
        scanned = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]),
                               scanned)

        def whole(key):
            """The leading layers' rows, then the periods'."""
            parts = led.get(key, []) + (
                [scanned[key]] if key in scanned else [])
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        counts = {key: whole(key) for key in sorted({*led, *scanned})}
        if "ssm_dt_max" in counts:      # scalars over the layers that have one
            counts.update(ssm_log_decay_min=counts["ssm_log_decay_min"].min(),
                          ssm_dt_max=counts["ssm_dt_max"].max())
        for least in ("delta_log_decay_min", "kda_log_decay_min"):
            if least in counts:
                counts[least] = counts[least].min()
        return h, counts

    if cfg.loops == 1:
        return stack(h)

    def walk(h, _):
        x = rmsnorm(stack(h)[0],
                    _gain(params["norm_f"], cfg).astype(h.dtype), cfg.rms_eps)
        return x, x

    # a scan over the walks around the scan over the periods: the weights
    # are constants of both, so a period is traced once whatever `loops`
    # and the backward pass adds each walk's gradient stack into ONE
    _, walks = lax.scan(walk, h, None, length=cfg.loops)
    return walks, {}


def _head(params, cfg: DecoderConfig, dtype):
    """[d_model, vocab] in `dtype`: the embedding's transpose, the head
    leaf, or its transpose where the leaf is kept outputs-as-rows."""
    if cfg.tied_head or cfg.head_rows:
        return params["embed" if cfg.tied_head else "head"].T.astype(dtype)
    return params["head"].astype(dtype)


def apply(params, tokens, cfg: DecoderConfig, bias=None):
    """tokens [B, T] -> float32 logits [B, T, vocab] (whole: for tests
    and small sizes; the loss below never builds them at once); with
    `cfg.loops` > 1 every walk's, [loops, B, T, vocab]."""
    h, _ = hidden(params, tokens, cfg, bias)
    x = h if cfg.loops > 1 else rmsnorm(
        h, _gain(params["norm_f"], cfg).astype(h.dtype), cfg.rms_eps)
    return jnp.dot(x, _head(params, cfg, x.dtype),
                   preferred_element_type=jnp.float32)


def mtp_hidden(params, h, tokens, cfg: DecoderConfig, bias=None):
    """The multi-token-prediction block. h: the last main block's output
    [B, T, D] (before the final norm) -> (the block's output after ITS
    final norm [B, T, D], whose row i predicts token i + 2; the block's
    routing counts). Position i joins h_i with the embedding of token
    i + 1; the last position, which has none, takes id 0 and is never
    scored (its routing is counted, as every position's is). `bias`:
    the whole selection bias; the block's row is the last."""
    p, cast = params["mtp"], functools.partial(jnp.asarray, dtype=h.dtype)
    with jax.named_scope("mtp"):
        following = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)

        def join(h, following, embed, p):
            e = embed[following].astype(h.dtype)
            both = jnp.concatenate(
                [rmsnorm(h, cast(p["norm_h"]), cfg.rms_eps),
                 rmsnorm(e, cast(p["norm_e"]), cfg.rms_eps)], axis=-1)
            return both @ cast(p["proj"])

        x = (jax.checkpoint(join) if cfg.remat else join)(
            h, following, params["embed"], p)
        layer = p["layer"] if bias is None else dict(
            p["layer"], expert_bias=bias[-1])
        x, counts = _block(cfg, *cfg.kinds[-1])(
            x, layer, _rope_for(tokens.shape[1], cfg))
        return rmsnorm(x, cast(p["norm_f"]), cfg.rms_eps), counts


def mtp_apply(params, tokens, cfg: DecoderConfig, bias=None):
    """tokens [B, T] -> the MTP head's float32 logits [B, T, vocab]; row
    i predicts token i + 2 (tests and small sizes)."""
    h, _ = hidden(params, tokens, cfg, bias)
    x, _ = mtp_hidden(params, h, tokens, cfg, bias)
    return jnp.dot(x, _head(params, cfg, x.dtype),
                   preferred_element_type=jnp.float32)


def loss_fn(params, tokens, cfg: DecoderConfig, bias=None):
    """Mean next-token cross-entropy over the B * (T - 1) positions that
    have a target -> (loss, counts). The mixers run at full T; the last
    position's logits are never formed. With an MTP block the loss is
    that mean plus `cfg.mtp_weight` times the block's mean over ITS
    B * (T - 2) positions (token i + 2 from position i), `counts` gains
    the block's row last and the two terms as `loss_main`, `loss_mtp`.
    With `cfg.loops` > 1 the loss is `loop_loss`'s."""
    h, counts = hidden(params, tokens, cfg, bias)
    if cfg.loops > 1:
        return loop_loss(h, tokens, params, cfg)
    x = rmsnorm(h, _gain(params["norm_f"], cfg).astype(h.dtype),
                cfg.rms_eps)
    if cfg.index_topk:
        main = _mean_nll(x, tokens, 1, params, cfg)[0]
        with jax.named_scope("index_loss"):
            index = counts["index_kl_sum"].sum() / (
                tokens.size * _index_layers(cfg))
        return main + cfg.index_loss_weight * index, {
            **counts, "loss_main": main, "loss_index": index}
    if not cfg.mtp:
        return _mean_nll(x, tokens, 1, params, cfg)[0], counts
    x_mtp, c = mtp_hidden(params, h, tokens, cfg, bias)
    counts = {**counts, **jax.tree.map(
        lambda a, b: jnp.concatenate([a, b[None]]),
        {name: counts[name] for name in c}, c)}
    main, head = _mean_nll(x, tokens, 1, params, cfg)
    second, _ = _mean_nll(x_mtp, tokens, 2, params, cfg, head)
    return main + cfg.mtp_weight * second, {
        **counts, "loss_main": main, "loss_mtp": second}


def _mean_nll(x, tokens, ahead: int, params, cfg: DecoderConfig, head=None):
    """Row i of x [B, T, D] (normed) against token i + `ahead` -> (the
    mean cross-entropy over the B * (T - ahead) positions that have a
    target, the head in x's dtype: cast once, handed to a second call)."""
    n = tokens.shape[0] * (tokens.shape[1] - ahead)
    with jax.named_scope("logits_loss"):
        x = x[:, :-ahead].reshape(n, -1)
        targets = tokens[:, ahead:].reshape(n)
        if head is None:
            head = _head(params, cfg, x.dtype)
        return _nll_sum(x, targets, None, head, cfg) / n, head


def _nll_sum(x, targets, weight, head, cfg: DecoderConfig):
    """The cross-entropy of row i of x [n, D] (normed) against
    `targets[i]`, times `weight[i]` (None: one), summed over the rows in
    chunks of `cfg.loss_chunk`, each chunk's logits recomputed in the
    backward pass. A `weight` [n, k] gives k sums of the one
    cross-entropy, [k]; a gradient reaches the weight as it does x."""
    chunk = min(cfg.loss_chunk, x.shape[0])
    pad = -x.shape[0] % chunk
    x = jnp.pad(x, ((0, pad), (0, 0)))
    if weight is None:
        weight = jnp.ones_like(targets, jnp.float32)
    weight = jnp.pad(weight, ((0, pad),) + ((0, 0),) * (weight.ndim - 1))
    targets = jnp.pad(targets, (0, pad))

    @jax.checkpoint
    def nll_sum(x, targets, weight):
        logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, targets[:, None], axis=-1)[:, 0]
        return (nll.reshape(nll.shape + (1,) * (weight.ndim - 1))
                * weight).sum(0)

    def body(total, part):
        return total + nll_sum(*part), None

    total, _ = lax.scan(body, jnp.zeros(weight.shape[1:], jnp.float32),
                        tuple(z.reshape(-1, chunk, *z.shape[1:])
                              for z in (x, targets, weight)))
    return total


# ----------------------------------------------------------------------
# a stack walked several times: the exit gate and the expected-exit loss
# ----------------------------------------------------------------------

def exit_distribution(walks, params):
    """Every walk's normed output [T, B, L, D] -> p [T, B, L] float32,
    the distribution over the walks a token exits after: the gate's
    `lam_t = sigmoid(w . x_t + b)` (ONE gate, float32), the survival
    `S_t = prod over j <= t of (1 - lam_j)`, `p(t) = lam_t S_(t-1)` for
    t < T and `p(T) = S_(T-1)`: the last walk takes what is left, its
    own lam is never used, and the T sum to one."""
    gate = params["exit_gate"]
    with jax.named_scope("exit_gate"):
        lam = jax.nn.sigmoid(jnp.dot(
            walks[:-1].astype(jnp.float32), gate["w"],
            precision=lax.Precision.HIGHEST) + gate["b"])
        survival = jnp.cumprod(1.0 - lam, axis=0)          # S_1 .. S_(T-1)
        before = jnp.concatenate([jnp.ones_like(lam[:1]), survival[:-1]])
        return jnp.concatenate([lam * before, survival[-1:]])


def loop_loss(walks, tokens, params, cfg: DecoderConfig):
    """The loss of a stack walked T = `cfg.loops` times, from `hidden`'s
    [T, B, L, D] -> (loss, counts). With `l_t[i]` walk t's next-token
    cross-entropy at position i (the last position unscored, n = B (L -
    1) targets): under `cfg.exit_gate` the expected-exit loss `sum over i
    of (sum over t of p_i(t) l_t[i] - cfg.exit_beta H(p_i)) / n`, `H` the
    entropy of `exit_distribution`'s p at the token — T passes of the
    chunked head, ONE scan over the walks, the weight a token carrying
    its gradient to the gate and, through the gate's input, to every
    walk before it; without a gate the last walk's mean cross-entropy.
    The counts, for the epoch counters (no gradient): `loop_nll` [T]
    (sum over i of l_t[i]), `exit_mass` [T] (sum of p(t)),
    `exit_entropy` (sum of H)."""
    t, b, length, _ = walks.shape
    n = b * (length - 1)
    with jax.named_scope("logits_loss"):
        head = _head(params, cfg, walks.dtype)
        targets = tokens[:, 1:].reshape(n)
        if not cfg.exit_gate:
            x = walks[-1, :, :-1].reshape(n, -1)
            return _nll_sum(x, targets, None, head, cfg) / n, {}
        scored = walks[:, :, :-1]
        p = exit_distribution(scored, params).reshape(t, n)
        entropy = -(p * jnp.log(jnp.maximum(
            p, jnp.finfo(jnp.float32).tiny))).sum(0)

        def one_walk(_, part):
            x, p_t = part       # the weighted sum, and the plain one
            return None, _nll_sum(x, targets, jnp.stack(
                [p_t, jnp.ones_like(p_t)], axis=1), head, cfg)

        _, sums = lax.scan(one_walk, None, (scored.reshape(t, n, -1), p))
    loss = (sums[:, 0].sum() - cfg.exit_beta * entropy.sum()) / n
    return loss, lax.stop_gradient({
        "loop_nll": sums[:, 1], "exit_mass": p.sum(1),
        "exit_entropy": entropy.sum()})


# ----------------------------------------------------------------------
# block diffusion: the noise, the doubled input, the loss
# ----------------------------------------------------------------------

NOISE_FLOOR = 1e-3      # the least masking rate a block draws


def diffusion_inputs(tokens, cfg: DecoderConfig, noise_seed, noise_step):
    """The noise of step `noise_step` on tokens [B, L] -> (the model's
    input `[x_0 ; x_t]` [B, 2 L], masked [B, L] bool, p [B, L] float32).
    With `k0, k1 = split(fold_in(key(noise_seed), noise_step))`: a block
    draws `t = uniform(k0)` and masks at rate `p = (1 - NOISE_FLOOR) t +
    NOISE_FLOOR` (one rate a block of `cfg.diffusion_block` tokens, the
    linear schedule with its floor); a token is masked where
    `uniform(k1) < p`; a masked token reads MASK, the slice's last id,
    which the data never draws."""
    b, length = tokens.shape
    block = cfg.diffusion_block
    if length % block:
        raise ValueError(f"{length} tokens are not whole blocks of {block}")
    k0, k1 = jax.random.split(jax.random.fold_in(
        jax.random.key(noise_seed), noise_step))
    t = jax.random.uniform(k0, (b, length // block), jnp.float32)
    p = jnp.repeat((1 - NOISE_FLOOR) * t + NOISE_FLOOR, block, axis=1)
    masked = jax.random.uniform(k1, (b, length), jnp.float32) < p
    noised = jnp.where(masked, cfg.vocab_size - 1, tokens)
    return jnp.concatenate([tokens, noised], axis=1), masked, p


def diffusion_loss(params, tokens, cfg: DecoderConfig, noise_seed,
                   noise_step, bias=None):
    """The block-diffusion loss of one step's noise on tokens [B, L]:
    `sum over masked (1 / p) CE(logits of the noised row i, x_0[i]) /
    (B L)` — no shift — and the counts, which gain `diffusion_masked`
    (the masked tokens) and `diffusion_weight_max` (the largest 1 / p
    that met one). 2 L rows go through the blocks; the final norm, the
    head and the chunked loss see the noised half only."""
    b, length = tokens.shape
    doubled, masked, p = diffusion_inputs(tokens, cfg, noise_seed,
                                          noise_step)
    h, counts = hidden(params, doubled, cfg, bias)
    x = rmsnorm(h[:, length:], _gain(params["norm_f"], cfg).astype(h.dtype),
                cfg.rms_eps)
    weight = jnp.where(masked, 1.0 / p, 0.0)
    with jax.named_scope("logits_loss"):
        total = _nll_sum(x.reshape(b * length, -1), tokens.reshape(-1),
                         weight.reshape(-1), _head(params, cfg, x.dtype), cfg)
    return total / (b * length), {
        **counts, "diffusion_masked": masked.sum().astype(jnp.float32),
        "diffusion_weight_max": weight.max()}


# ----------------------------------------------------------------------
# model state: counters that leave the step without a sync, and the
# selection bias
# ----------------------------------------------------------------------

def counters_init(cfg: DecoderConfig):
    """The model state of the operator's stateful form: `{"epoch_counters":
    {...}}`, scalars the step updates on the device, zeroed by the
    operator when an epoch starts and read once in `train.sync`, each
    onto that span under its key. A pattern with an `experts` layer
    counts:

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
    of 2**31 / (tokens x top_k x layers) steps. The MoE layers include
    the MTP block, and a configuration that has one also counts
    `loss_main` and `loss_mtp` (the last step's two terms, the second
    before its weight), `moe_rows_static` (the rows the grouped matmul's
    arrays hold, the worst case: `parallel/moe.py::static_rows` x MoE
    layers x steps), `moe_rows_filled` (those that held an assignment)
    and `moe_rows_walked` (the rows the expert blocks ran over: the
    rung of `parallel/moe.py::row_ladder` each layer's routing chose,
    summed the same way); `cfg.count_rows` asks for the three rows
    counters without one. A configuration with an ssm mixer counts `ssm_log_decay_min`
    (the most negative sum of dt A over one chunk of the scan that any
    head of any layer saw in the epoch: below about -87 a float32 chunk
    forgets the state that entered it entirely) and `ssm_dt_max` (the
    largest dt). A configuration trained by block diffusion counts
    `diffusion_masked` (tokens that were masked, summed over the steps),
    `diffusion_targets` (tokens that could have been: B x L a step; the
    quotient's expectation is (1 + NOISE_FLOOR) / 2) and
    `diffusion_weight_max` (the largest 1 / p that met a masked token in
    the epoch: what one target can weigh), and its state holds, OUTSIDE
    the epoch counters, `noise_seed` and `noise_step` (`state_init`).
    A DENSE pattern (no `experts` layer anywhere) makes none of the
    `moe_*` counters and counts no routing. A stack walked `cfg.loops` =
    T > 1 times under an exit gate counts, float32 sums over the epoch's
    steps: `loop_nll_1` .. `loop_nll_T` (walk t's cross-entropy summed
    over the tokens scored, before any weight), `exit_mass_1` ..
    `exit_mass_T` (the exit distribution's p(t) summed over them: the T
    add up to `loop_targets`), `exit_entropy` (its entropy summed; at
    most `loop_targets` x log T) and `loop_targets` (tokens scored: B x
    (L - 1) a step). Under `cfg.attn_gate`, a kind of the pattern:
    `attn_gate_sum_full` / `_window` (the gates sigmoid(x . w_g) summed
    over tokens, heads, that kind's layers and the epoch's steps) and
    `attn_gate_count_full` / `_window` (how many were summed: the
    quotient is how open the gate stands, one half at seeded weights;
    under the `"element"` gate both count head dimensions too).
    With a delta mixer: `delta_log_decay_min` (the least sum of the log
    decay g over one chunk that any value head of any layer saw in the
    epoch: what the kernels' masked exponent has to survive),
    `delta_beta_sum` / `delta_beta_count` (the write strengths summed
    over tokens, value heads, delta layers and steps, and how many).
    With a kda mixer, under names of its own: `kda_log_decay_min` (the
    least sum of the log decay over one chunk that any CHANNEL of any
    head and layer saw), `kda_decay_spread_sum` / `_count` (a chunk's
    sum of the log decay, its largest over a head's channels minus its
    least, summed over chunks, heads, kda layers and steps, and how
    many: what a decay a head cannot have; zero would mean the channels
    forget alike), `kda_beta_sum` / `_count` (the write strengths, over
    tokens, heads, layers and steps) and `kda_gate_sum` / `_count` (the
    output gates sigmoid(.), over tokens and value channels too).
    Under `cfg.shared_gate`: `shared_gate_sum` / `shared_gate_count`
    (the shared expert's gates over tokens, expert layers and steps).
    Under `cfg.index_topk`, float32 sums over batch rows, `full` layers
    and the epoch's steps: `index_pairs_selected` / `index_pairs_causal`
    (the pairs the indexer kept over those at or below the diagonal:
    sum of min(t + 1, topk) over t (t + 1) / 2, that the selection
    engages), `index_pairs_beyond_window` (selected pairs whose key lies
    `index_topk` positions or more before the query: what a window of
    that many keys cannot hold), `index_tiles_visited` /
    `index_tiles_causal` (the forward kernel's score tiles that hold a
    selected pair, which it runs, over the tiles of its causal walk:
    what the selection saves on this chip), `index_kl_sum` /
    `index_kl_count` (the indexer's KL summed over rows, and the rows),
    and `loss_main`, `loss_index` (the last step's two terms, the second
    before its weight).
    The configurations from before each of these keep the state tree
    their recorded programs were lowered with."""
    f32 = functools.partial(jnp.zeros, (), jnp.float32)
    i32 = functools.partial(jnp.zeros, (), jnp.int32)
    counters = {}
    if cfg.moe_layers:
        counters.update({
            "moe_assignments": f32(), "moe_assignments_held": f32(),
            "moe_assignments_dropped": f32(), "moe_expert_tokens_max": i32(),
            "moe_expert_tokens_mean": f32(), "moe_experts_held": i32(),
            "moe_experts_total": i32(), "moe_steps": i32()})
    if cfg.exit_gate:
        counters.update({f"{name}_{t + 1}": f32() for t in range(cfg.loops)
                         for name in ("loop_nll", "exit_mass")},
                        exit_entropy=f32(), loop_targets=f32())
    if cfg.mtp:
        counters.update(loss_main=f32(), loss_mtp=f32())
    if cfg.mtp or cfg.count_rows:
        counters.update(moe_rows_static=f32(), moe_rows_filled=f32(),
                        moe_rows_walked=f32())
    if "ssm" in cfg.attention + cfg.lead_attention:
        counters.update(ssm_log_decay_min=f32(), ssm_dt_max=f32())
    if cfg.diffusion_block:
        counters.update(diffusion_masked=f32(), diffusion_targets=f32(),
                        diffusion_weight_max=f32())
    if cfg.attn_gate:
        counters.update({f"attn_gate_{what}_{kind}": f32()
                         for kind in _attention_layers(cfg)
                         for what in ("sum", "count")})
    if _delta_layers(cfg):
        counters.update(delta_log_decay_min=f32(), delta_beta_sum=f32(),
                        delta_beta_count=f32())
    if cfg.shared_gate:
        counters.update(shared_gate_sum=f32(), shared_gate_count=f32())
    if _kda_layers(cfg):
        counters.update(kda_log_decay_min=f32(), **{
            f"kda_{name}_{what}": f32() for what in ("sum", "count")
            for name in ("decay_spread", "beta", "gate")})
    if cfg.index_topk:
        counters.update({name: f32() for name in _INDEX_COUNTERS},
                        loss_main=f32(), loss_index=f32())
    return {"epoch_counters": counters}


_INDEX_COUNTERS = (
    "index_pairs_selected", "index_pairs_causal", "index_pairs_beyond_window",
    "index_tiles_visited", "index_tiles_causal", "index_kl_sum",
    "index_kl_count")


def _index_layers(cfg: DecoderConfig) -> int:
    """The layers whose attention an indexer steers."""
    return sum(a == "full" for a, _ in cfg.kinds) if cfg.index_topk else 0


def _delta_layers(cfg: DecoderConfig) -> int:
    """The layers whose mixer is the delta rule."""
    return sum(a == "delta" for a, _ in cfg.kinds)


def _kda_layers(cfg: DecoderConfig) -> int:
    """The layers whose mixer is the per-channel delta rule."""
    return sum(a == "kda" for a, _ in cfg.kinds)


def _attention_layers(cfg: DecoderConfig) -> dict:
    """attention kind -> how many layers of the pattern have it."""
    return {kind: n for kind in ATTENTION_KINDS
            if (n := sum(a == kind for a, _ in cfg.kinds))}


def step_facts(cfg: DecoderConfig, batch_shape) -> dict:
    """What is known of a step on a batch [B, T] without running it, for
    the `train.dispatch` span (`loss_fn.step_facts`, read by the
    operator): with an ssm mixer `ssm_layers` and `ssm_chunks`, the
    chunks the scan walks a step (layers x sequences x T / chunk; every
    head walks each); with a delta mixer `delta_layers`, `delta_chunks`
    (the same product at the rule's chunk of 64), `delta_heads` (the
    value heads that walk each) and `delta_heads_paired` (those of them
    whose chunk inverse runs two to a product:
    `ops.gated_delta.paired_heads` a key head); with a kda mixer
    `kda_layers`, `kda_chunks` and `kda_heads`, the same under names of
    its own; under block diffusion
    `diffusion_block`, `diffusion_rows` (rows through the blocks a step:
    B x 2 L) and
    `attention_tiles_visited` / `attention_tiles_plane` (the score tiles
    the forward kernel's loops walk of one head's 2 L x 2 L plane, and
    the plane's: `ops.attention.diffusion_tiles`, the kernel's own
    bounds); where the stack is walked more than once `loops`,
    `layer_passes` (loops x layers: the blocks a step runs forward) and
    `head_passes` (passes of the chunked head: one a walk under an exit
    gate, else one); under `cfg.by_kind` `attention_heads_full` /
    `_window` (the kinds the pattern has), `rope_dim` (the turned width,
    where ONE kind is named), `rope_scaling` (`"yarn:64"`:
    the kinds' scaling rules, where one has one) and, with window
    layers, `attention_window`, `window_scores_inside` (a step's score
    entries inside causal AND window on those layers: batch x heads x
    layers x the plane's, the forward's twice for its rematerialised
    copy and the backward's once) and `window_scores_visited` (the
    entries of the tiles `flash_fwd` and `flash_bwd_fused` walk for
    them, from the kernels' own bounds: `ops.attention.window_scores`);
    with a layer that calls `flash_attention` (`full`, `window`,
    `latent`) `attention_tiles_walked` and `attention_tiles_unmasked`
    (the score tiles `flash_fwd` walks a step and those of them it runs
    with no mask, over sequences, heads, layer passes, the MTP block and
    a rematerialised block's second forward:
    `ops.attention.forward_tiles`, the runs the kernel follows); under
    `cfg.index_topk` `index_topk`, `index_rows` (the query rows a step
    selects keys for: batch x T x `full` layers, 0 where T <= topk) and
    `index_tile` (`"256x512"`: the forward tile whose counts the
    selection hands the kernels) and `index_kl_runs` (the passes of
    `index_kl` a step: one a `full` layer, two where a rematerialised
    block does not keep what the pass made; the backward rule runs
    none); nothing otherwise."""
    b, t = batch_shape
    facts = {}
    unmasked = walked = 0
    layers = [a for a, _ in cfg.kinds + cfg.kinds[-1:] * cfg.mtp]
    for kind in ATTENTION_KINDS + ("latent",):
        if kind not in layers:
            continue
        heads, width = (cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim) \
            if kind == "latent" else (cfg.heads_of(kind), cfg.head_dim)
        plain, all_ = forward_tiles(
            2 * t if cfg.diffusion_block else t, width, cfg.dtype,
            cfg.attn_block_q, cfg.attn_block_k,
            cfg.window if kind == "window" else None,
            cfg.diffusion_block or None)
        # a pass of a block; a rematerialised one runs it twice
        planes = (b * heads * layers.count(kind) * cfg.loops
                  * (1 + bool(cfg.remat)))
        unmasked, walked = unmasked + planes * plain, walked + planes * all_
    if walked:
        facts.update(attention_tiles_unmasked=unmasked,
                     attention_tiles_walked=walked)
    if cfg.loops > 1:
        facts.update(loops=cfg.loops, layer_passes=cfg.loops * cfg.n_layers,
                     head_passes=cfg.loops if cfg.exit_gate else 1)
    layers = sum(a == "ssm" for a, _ in cfg.kinds)
    if layers:
        facts.update(ssm_layers=layers,
                     ssm_chunks=layers * b * (t // cfg.ssm_chunk))
    layers = _delta_layers(cfg)
    if layers:
        facts.update(delta_layers=layers,
                     delta_chunks=layers * b * (t // DELTA_CHUNK),
                     delta_heads=cfg.delta_value_heads,
                     delta_heads_paired=cfg.delta_key_heads * paired_heads(
                         cfg.delta_value_heads // cfg.delta_key_heads,
                         DELTA_CHUNK))
    layers = _kda_layers(cfg)
    if layers:
        facts.update(kda_layers=layers,
                     kda_chunks=layers * b * (t // DELTA_CHUNK),
                     kda_heads=cfg.delta_key_heads)
    if cfg.index_topk:
        facts.update(
            index_topk=cfg.index_topk,
            index_rows=b * t * _index_layers(cfg) if t > cfg.index_topk
            else 0,
            index_tile=f"{min(cfg.attn_block_q, t)}x"
                       f"{min(cfg.attn_block_k, t)}",
            # as `_block` builds the blocks: a second pass a layer where
            # it is rematerialised and keeps nothing of the first
            index_kl_runs=_index_layers(cfg) * (1 + (
                bool(cfg.remat) and not _kept_across_remat(cfg, "full"))))
    if cfg.diffusion_block:
        visited, plane = diffusion_tiles(
            2 * t, cfg.diffusion_block, cfg.attn_block_q, cfg.attn_block_k)
        facts.update(diffusion_block=cfg.diffusion_block,
                     diffusion_rows=b * 2 * t,
                     attention_tiles_visited=visited,
                     attention_tiles_plane=plane)
    if cfg.by_kind:
        at = _attention_layers(cfg)
        facts.update({f"attention_heads_{kind}": cfg.heads_of(kind)
                      for kind in at})
        if len(cfg.by_kind) == 1:   # one kind: one turned width to name
            facts["rope_dim"] = cfg.by_kind[0][1].rope_dim
        scaled = [f"yarn:{rule.yarn[0]:g}" for _, rule in cfg.by_kind
                  if rule.yarn]
        if scaled:
            facts["rope_scaling"] = ",".join(scaled)
        if "window" in at:
            inside, fwd, bwd = window_scores(
                t, cfg.window, cfg.head_dim, cfg.dtype, cfg.attn_block_q,
                cfg.attn_block_k)
            planes = b * at["window"] * cfg.heads_of("window")
            facts.update(attention_window=cfg.window,
                         window_scores_inside=planes * 3 * inside,
                         window_scores_visited=planes * (2 * fwd + bwd))
    return facts


def state_init(key, cfg: DecoderConfig):
    """`counters_init`, and where the routing has a selection bias:
    `expert_bias` [MoE layers, n_experts] float32, seeded normal(0,
    init_std) from the same key as the parameters (a checkpoint's biases
    are not zero; at zero the first step would not see the rule), and
    its two counters; under block diffusion `noise_seed` (int32, drawn
    here from the same key: what `--seed` the weights came from, the
    noise comes from) and `noise_step` (int32, 0: the steps whose noise
    has been drawn)."""
    state = counters_init(cfg)
    if cfg.diffusion_block:
        state.update(
            noise_seed=jax.random.randint(
                jax.random.fold_in(key, _NOISE_KEY), (), 0,
                jnp.iinfo(jnp.int32).max, jnp.int32),
            noise_step=jnp.zeros((), jnp.int32))
    if cfg.routing != "sigmoid_bias":
        return state
    f32 = functools.partial(jnp.zeros, (), jnp.float32)
    state["epoch_counters"].update(
        moe_assignments_bias_moved=f32(), moe_bias_abs_max=f32())
    state["expert_bias"] = cfg.init_std * jax.random.normal(
        jax.random.split(key, 12)[11],
        (cfg.moe_layers, cfg.n_experts), jnp.float32)
    return state


def _routing_counters(old, counts, cfg: DecoderConfig, bias) -> dict:
    """The `moe_*` counters after a step that counted `counts`."""
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
    return new


def stateful_loss(params, state, tokens, cfg: DecoderConfig):
    """`loss_fn` in the operator's stateful form: the step's counts go
    into the state's running ones, and the selection bias, where there
    is one, makes its step after the loss (`parallel/moe.py::
    balance_bias`). A dense pattern counts no routing; a looped one
    under an exit gate counts what `loop_loss` found."""
    bias = state.get("expert_bias")
    if cfg.diffusion_block:
        loss, counts = diffusion_loss(params, tokens, cfg,
                                      state["noise_seed"],
                                      state["noise_step"], bias)
    else:
        loss, counts = loss_fn(params, tokens, cfg, bias)
    old = state["epoch_counters"]
    new = _routing_counters(old, counts, cfg, bias) if cfg.moe_layers else {}
    if cfg.exit_gate:
        for name in ("loop_nll", "exit_mass"):
            new.update({f"{name}_{t + 1}": old[f"{name}_{t + 1}"]
                        + counts[name][t] for t in range(cfg.loops)})
        new.update(
            exit_entropy=old["exit_entropy"] + counts["exit_entropy"],
            loop_targets=old["loop_targets"] + float(
                tokens.shape[0] * (tokens.shape[1] - 1)))
    if cfg.mtp:
        new.update(loss_main=counts["loss_main"],
                   loss_mtp=counts["loss_mtp"])
    if "ssm_dt_max" in old:
        new.update(
            ssm_log_decay_min=jnp.minimum(old["ssm_log_decay_min"],
                                          counts["ssm_log_decay_min"]),
            ssm_dt_max=jnp.maximum(old["ssm_dt_max"], counts["ssm_dt_max"]))
    if cfg.diffusion_block:
        new.update(
            diffusion_masked=old["diffusion_masked"]
            + counts["diffusion_masked"],
            diffusion_targets=old["diffusion_targets"] + float(tokens.size),
            diffusion_weight_max=jnp.maximum(old["diffusion_weight_max"],
                                             counts["diffusion_weight_max"]))
    rows = tokens.size * (2 if cfg.diffusion_block else 1)
    if cfg.attn_gate:
        for kind, layers in _attention_layers(cfg).items():
            new.update({
                f"attn_gate_sum_{kind}": old[f"attn_gate_sum_{kind}"]
                + counts[f"attn_gate_sum_{kind}"].sum(),
                f"attn_gate_count_{kind}": old[f"attn_gate_count_{kind}"]
                + float(rows * layers * cfg.heads_of(kind) * (
                    cfg.head_dim if cfg.attn_gate == "element" else 1))})
    if "delta_beta_sum" in old:
        new.update(
            delta_log_decay_min=jnp.minimum(old["delta_log_decay_min"],
                                            counts["delta_log_decay_min"]),
            delta_beta_sum=old["delta_beta_sum"]
            + counts["delta_beta_sum"].sum(),
            delta_beta_count=old["delta_beta_count"] + float(
                rows * _delta_layers(cfg) * cfg.delta_value_heads))
    if "kda_beta_sum" in old:
        heads = rows * _kda_layers(cfg) * cfg.delta_key_heads
        new["kda_log_decay_min"] = jnp.minimum(
            old["kda_log_decay_min"], counts["kda_log_decay_min"])
        for name, count in (("decay_spread", heads // DELTA_CHUNK),
                            ("beta", heads),
                            ("gate", heads * cfg.delta_value_dim)):
            new.update({
                f"kda_{name}_sum": old[f"kda_{name}_sum"]
                + counts[f"kda_{name}_sum"].sum(),
                f"kda_{name}_count": old[f"kda_{name}_count"]
                + float(count)})
    if cfg.index_topk:
        b, t = tokens.shape
        layers = _index_layers(cfg)
        causal = float(layers * b * (t * (t + 1) // 2))
        tiles = float(layers * b * forward_tiles(
            t, cfg.head_dim, cfg.dtype, cfg.attn_block_q,
            cfg.attn_block_k)[1])
        selects = "index_pairs_selected" in counts  # else T <= index_topk
        step = {
            "index_pairs_selected": counts["index_pairs_selected"].sum()
            if selects else causal,
            "index_pairs_causal": causal,
            "index_pairs_beyond_window":
            counts["index_pairs_beyond_window"].sum() if selects else 0.0,
            "index_tiles_visited": counts["index_tiles_visited"].sum()
            if selects else tiles,
            "index_tiles_causal": tiles,
            "index_kl_sum": lax.stop_gradient(counts["index_kl_sum"].sum()),
            "index_kl_count": float(layers * b * t)}
        new.update({name: old[name] + step[name] for name in step},
                   loss_main=lax.stop_gradient(counts["loss_main"]),
                   loss_index=lax.stop_gradient(counts["loss_index"]))
    if cfg.shared_gate:
        new.update(
            shared_gate_sum=old["shared_gate_sum"]
            + counts["shared_gate_sum"].sum(),
            shared_gate_count=old["shared_gate_count"] + float(
                rows * sum(m == "experts" for _, m in cfg.kinds)))
    if "moe_rows_static" in old:
        new.update(
            moe_rows_static=old["moe_rows_static"] + float(
                cfg.moe_layers * static_rows(
                    rows * cfg.top_k, cfg.held[1], cfg.gmm_tile)),
            moe_rows_filled=old["moe_rows_filled"] + (
                counts["held"] - counts["dropped"]).sum().astype(jnp.float32),
            moe_rows_walked=old["moe_rows_walked"]
            + counts["rows_walked"].sum().astype(jnp.float32))
    state = {**state, "epoch_counters": new}
    if cfg.diffusion_block:
        state["noise_step"] = state["noise_step"] + 1
    if bias is not None:
        bias = balance_bias(bias, counts["routed"], cfg.bias_rate)
        new["moe_bias_abs_max"] = jnp.abs(bias).max()
        state["expert_bias"] = bias
    return loss, state
