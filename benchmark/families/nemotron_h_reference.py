"""Plain reference of the ``nemotron_h`` family: forward pass and loss in
``jax.numpy``, float32, matmul precision "highest". No kernel, no
chunks, no grouping, no sort, no remat: a Python loop over the blocks,
the state-space scan as the PLAIN RECURRENCE walked position by position
(``lax.scan`` over t carries the state; nothing of the chunked form), a
dense mask on blocks of the attention scores, EVERY held expert applied
to every token under a dense mask of who chose it. Nothing is imported
from ``ray_tpu``.

Written from the published configuration of
NVIDIA-Nemotron-3-Nano-30B-A3B (``config.json``, ``model_type``
``nemotron_h``), the Mamba-2 paper (arXiv:2405.21060) and the Nemotron-H
report (arXiv:2504.03624). Every block is ``h <- h + f(RMSNorm(h))``
(weight only, eps 1e-5) with f one of three, u the normed input
``[T, 2688]`` of one sequence:

    M (Mamba-2; 64 heads of 64, 8 groups, state 128, 4 taps):
      [z 4096 | xBC 6144 | dt 64] = u W_in
      xBC = silu(conv4(xBC) + b)     depthwise, causal: position t sees
                                     t-3..t, zeros before the sequence
      [x 4096 | B 1024 | C 1024] = xBC    x: 64 heads of 64; B, C: 8
                                     groups of 128, head h reads h // 8
      D_t = softplus(dt_t + dt_bias)      a scalar a head and position
      A   = -exp(A_log)
      S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T      [64, 128] a head
      y_t = S_t C_t + D x_t
      g   = y * silu(z)              the gate BEFORE the norm
      g   = RMSNorm over each of the 8 groups of 512 channels, weight 4096
      f   = g W_out
    * (attention; 32 query and 2 key/value heads of 128, no positions):
      f   = softmax(q k^T / sqrt(128) + causal) v W_o    head h reads
                                     key/value head h // 16
    E (experts):
      s   = sigmoid(u W_r)           float32, all 128 experts
      S   = top6(s + b)              b: the block's selection bias, choice
                                     only (n_group 1: no group limit)
      p_e = 2.5 * s_e / (sum_{S} s + 1e-6)       the UNBIASED s
      f   = sum over e in S AND e in Held of p_e E_e(u) + Shared(u)
      E_e(u) = W_down relu(W_up u)^2   width 1856, shared 3712: UNGATED
    logits = RMSNorm_f(h_L) W_head   untied, over the vocabulary slice

after the loss, once a step (``bias_update``; arXiv:2408.15664):
    b_e <- b_e + u sign(mean_e' n_e' - n_e)      n_e: assignments expert
                                     e of all 128 got in that block, u 1e-3

Departures and choices, each under ``assumed`` in the configuration
file: no positional turn in the attention; ``Held`` = experts 0..7 of
128 (rank 0 of sixteen chips) and what the others would add is left out
here as in the program, the shared expert being what every chip
computes alike; the vocabulary is the slice held; the routing's 1e-6;
the bias rule and its rate. The parameter tree is the program's, whose
LAYERS are a mixer block and the feed-forward block after it (or either
alone): every leaf stacked over the layers that have it, in layer order,
the held experts along the next axis; ``ssm_in`` and ``w_up`` are kept
with their OUTPUTS as rows (``[10304, 2688]``, ``[8, 1856, 2688]``: the
model width is every matrix's minor dimension). Leaf names map as W_in =
``ssm_in``, the taps and b = ``ssm_conv``, ``ssm_conv_bias``, W_out =
``ssm_out``, the grouped norm's weight = ``ssm_norm``; W_up, W_down =
``w_up``, ``w_down`` (``ws_*`` the shared expert's); a mixer block's
norm is ``norm1``, a feed-forward block's ``norm2``.

``MUTATIONS`` are alternatives the configuration did NOT take; the
tests show the comparison tells each apart.

It computes one sequence at a time, attention ``HEAD_BLOCK`` heads and
``QUERY_BLOCK`` queries at a time (a ``[8, 4096, 8192]`` float32 score
block is 1.07 GB), the eight held experts as one product (``[8, 8192,
1856]`` float32 is 0.49 GB), so that it fits beside the training state
on the chip; the recurrence is 8192 steps a mixer block, each a handful
of small operations on a 2 MB state."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.families.smallthinker_reference import _rmsnorm

ROUTING_EPS = 1e-6
QUERY_BLOCK = 4096
HEAD_BLOCK = 8

MUTATIONS = (
    "the gate after the norm", "one norm over all channels",
    "the convolution's bias left out", "no SiLU after the convolution",
    "D left out", "dt without its bias", "B and C swapped",
    "gated experts' activation (plain ReLU)", "the shared expert left out",
    "factor 1", "rotary positions")

_MIXER = {"M": "ssm", "*": "full"}
_MLP = {"E": "experts", "-": "dense"}


def scan(x, delta, a, b, c, d):
    """The recurrence, position by position. x: [T, H, P]; delta: [T, H];
    a, d: [H]; b, c: [T, H, N] (each head's own group's) -> y [T, H, P]."""

    def step(state, at):
        x_t, d_t, b_t, c_t = at
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, (state * c_t[:, None, :]).sum(-1) + d[:, None] * x_t

    h, p = x.shape[1:]
    _, y = lax.scan(step, jnp.zeros((h, p, b.shape[-1]), x.dtype),
                    (x, delta, b, c))
    return y


def mamba_mixer(u, p, model, mutate: str = ""):
    """The Mamba-2 block's f. u: [T, D] -> (f [T, D], delta A [T, H])."""
    t = u.shape[0]
    h, hp, g, n = model["mamba_num_heads"], model["mamba_head_dim"], \
        model["n_groups"], model["ssm_state_size"]
    inner, k = h * hp, model["conv_kernel"]
    zxbcdt = u @ p["ssm_in"].T
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * g * n], \
        zxbcdt[:, 2 * inner + 2 * g * n:]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), u.dtype), xbc])
    conv = sum(p["ssm_conv"][j] * padded[j:j + t] for j in range(k))
    if mutate != "the convolution's bias left out":
        conv = conv + p["ssm_conv_bias"]
    xbc = conv if mutate == "no SiLU after the convolution" \
        else jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, h, hp)
    b, c = (jnp.repeat(xbc[:, lo:lo + g * n].reshape(t, g, n), h // g, axis=1)
            for lo in (inner, inner + g * n))
    if mutate == "B and C swapped":
        b, c = c, b
    delta = jax.nn.softplus(
        dt if mutate == "dt without its bias" else dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    d = jnp.zeros_like(p["D"]) if mutate == "D left out" else p["D"]
    y = scan(x, delta, a, b, c, d).reshape(t, inner)
    gate, eps = jax.nn.silu(z), model["layer_norm_epsilon"]

    def norm(v):
        groups = 1 if mutate == "one norm over all channels" else g
        v = v.reshape(t, groups, -1)
        return (v * lax.rsqrt((v * v).mean(-1, keepdims=True) + eps)
                ).reshape(t, inner) * p["ssm_norm"]

    y = norm(y) * gate if mutate == "the gate after the norm" \
        else norm(y * gate)
    return y @ p["ssm_out"], delta * a


def _rope(x, theta: float):
    """Rotate-half positions, for the mutation only: the configuration
    turns nothing."""
    t, _, dim = x.shape
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention_mixer(u, p, model, mutate: str = ""):
    """Causal grouped-query attention, a block of heads and of queries at
    a time under a dense mask. u: [T, D] -> [T, D]."""
    t = u.shape[0]
    n_q, n_kv, hd = model["num_attention_heads"], \
        model["num_key_value_heads"], model["head_dim"]
    q = (u @ p["wq"]).reshape(t, n_q, hd)
    k = (u @ p["wk"]).reshape(t, n_kv, hd)
    v = (u @ p["wv"]).reshape(t, n_kv, hd)
    if mutate == "rotary positions":
        q, k = _rope(q, float(model["rope_theta"])), \
            _rope(k, float(model["rope_theta"]))
    k, v = (jnp.repeat(z, n_q // n_kv, axis=1) for z in (k, v))
    j = jnp.arange(t)[None, :]
    heads = []
    for g in range(0, n_q, HEAD_BLOCK):
        parts = []
        for lo in range(0, t, QUERY_BLOCK):
            qb = q[lo:lo + QUERY_BLOCK, g:g + HEAD_BLOCK]
            i = (lo + jnp.arange(qb.shape[0]))[:, None]
            s = jnp.einsum("qhd,khd->hqk", qb,
                           k[:, g:g + HEAD_BLOCK]) * hd ** -0.5
            s = jnp.where((i >= j)[None], s, -jnp.inf)
            parts.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                    v[:, g:g + HEAD_BLOCK]))
        heads.append(jnp.concatenate(parts, axis=0))
    return jnp.concatenate(heads, axis=1).reshape(t, n_q * hd) @ p["wo"]


def _act(x, mutate: str = ""):
    r = jax.nn.relu(x)
    return r if mutate == "gated experts' activation (plain ReLU)" else r * r


def routed(u, r, p, bias, *, first: int, k_active: int, factor: float,
           mutate: str = ""):
    """The routed experts' part of a block, and who was chosen. u: [T, D];
    r: [T, 128] the router's product over ALL experts; bias: [128]; p
    holds the held experts' weights, expert e of them being expert
    `first + e` of the router. Returns (m [T, D], n [128]: the
    assignments each of all experts got)."""
    s = jax.nn.sigmoid(r)
    _, chosen = lax.top_k(s + bias, k_active)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = factor * picked / (picked.sum(-1, keepdims=True) + ROUTING_EPS)
    held = first + jnp.arange(p["w_up"].shape[0])
    # p_e of every token for every held expert: 0 where it was not chosen
    p_e = (weight[:, :, None] * (chosen[:, :, None] == held)).sum(1)  # [T, E]
    act = _act(jnp.einsum("td,efd->etf", u, p["w_up"]), mutate)
    m = (p_e.T[:, :, None]
         * jnp.einsum("etf,efd->etd", act, p["w_down"])).sum(0)
    n = (chosen[:, :, None] == jnp.arange(r.shape[-1])).sum((0, 1))
    return m, n


def experts(u, p, bias, model, mutate: str = "", first: int | None = None):
    """The expert block's f on its normed input u [T, D] -> (f, the routed
    part alone, n)."""
    m, n = routed(
        u, u @ p["router"], p, bias,
        first=model["held_experts_first"] if first is None else first,
        k_active=model["num_experts_per_tok"],
        factor=1.0 if mutate == "factor 1"
        else model["routed_scaling_factor"], mutate=mutate)
    shared = 0.0 if mutate == "the shared expert left out" \
        else _act(u @ p["ws_up"], mutate) @ p["ws_down"]
    return m + shared, m, n


def layers(model: dict) -> list[tuple[str, str, dict]]:
    """The blocks read as the program's layers: (mixer or "none", mlp or
    "none", {leaf group: the layer's row in that group's stacks}). A
    mixer block and the feed-forward block after it are one layer."""
    pattern = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    out, seen, i = [], {}, 0

    def row(*groups):
        at = {g: seen.get(g, 0) for g in groups}
        seen.update((g, n + 1) for g, n in at.items())
        return at

    while i < len(pattern):
        if pattern[i] in _MLP:
            mlp = _MLP[pattern[i]]
            out.append(("none", mlp, row("mlp", mlp)))
        elif i + 1 < len(pattern) and pattern[i + 1] in _MLP:
            mixer, mlp = _MIXER[pattern[i]], _MLP[pattern[i + 1]]
            out.append((mixer, mlp, row("mixer", mixer, "mlp", mlp)))
            i += 1
        else:
            mixer = _MIXER[pattern[i]]
            out.append((mixer, "none", row("mixer", mixer)))
        i += 1
    return out


_GROUP_OF = {
    "norm1": "mixer", "norm2": "mlp",
    "ssm_in": "ssm", "ssm_conv": "ssm", "ssm_conv_bias": "ssm",
    "A_log": "ssm", "D": "ssm", "dt_bias": "ssm", "ssm_norm": "ssm",
    "ssm_out": "ssm",
    "wq": "full", "wk": "full", "wv": "full", "wo": "full",
    "router": "experts", "w_up": "experts", "w_down": "experts",
    "ws_up": "experts", "ws_down": "experts",
    "w3": "dense", "w2": "dense"}


def forward(params, bias, tokens, model, mutate: str = ""):
    """ONE sequence. tokens: [T]; bias: [expert blocks, 128]. Returns
    (logits [T, V], n [expert blocks, 128], the most negative sum of
    delta A over a chunk of `chunk_size` positions any head of any mixer
    block saw, the largest delta)."""
    eps, q = model["layer_norm_epsilon"], model["chunk_size"]
    h = params["embed"][tokens]
    counts, decays, deltas = [], [], []
    for mixer, mlp, row in layers(model):
        p = {name: leaf[row[_GROUP_OF[name]]]
             for name, leaf in params["layers"].items()
             if _GROUP_OF[name] in row}
        if mixer == "ssm":
            f, da = mamba_mixer(_rmsnorm(h, p["norm1"], eps), p, model,
                                mutate)
            h = h + f
            decays.append(da.reshape(-1, q, da.shape[1]).sum(1).min())
            deltas.append((da / -jnp.exp(p["A_log"])).max())
        elif mixer == "full":
            h = h + attention_mixer(_rmsnorm(h, p["norm1"], eps), p, model,
                                    mutate)
        if mlp == "experts":
            f, _, n = experts(_rmsnorm(h, p["norm2"], eps), p,
                              bias[row["experts"]], model, mutate)
            h = h + f
            counts.append(n)
        elif mlp == "dense":
            u = _rmsnorm(h, p["norm2"], eps)
            h = h + _act(u @ p["w3"], mutate) @ p["w2"]
    logits = _rmsnorm(h, params["norm_f"], eps) @ params["head"]
    return logits, jnp.stack(counts), jnp.stack(decays).min(), \
        jnp.stack(deltas).max()


def nll_sum(params, bias, tokens, model, mutate: str = ""):
    """(summed next-token cross-entropy of ONE sequence, n). The
    softmax and the sum over the targets are float32 whatever the
    blocks computed in."""
    logits, n, _, _ = forward(params, bias, tokens, model, mutate)
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).sum(), n


def bias_update(bias, n, rate: float):
    """The loss-free balancing rule. bias, n: [expert blocks, 128]; n the
    assignments every expert got from the whole batch."""
    n = n.astype(jnp.float32)
    return bias + rate * jnp.sign(n.mean(-1, keepdims=True) - n)


def loss(init, batch, model: dict, dtype=jnp.float32,
         mutate: str = "") -> float:
    """Mean next-token cross-entropy of the whole batch, one sequence at
    a time. `init` is what the family's `model_init` returns:
    (parameters, the model state, whose `expert_bias` is read). `dtype`
    other than float32 is the control that a lower precision is told
    apart (`benchmark/tools/reference_control.py`): weights, every
    block's activations, the recurrence's state, the router and the
    softmax of the attention in `dtype`, the loss's own softmax and sums
    still float32 — a loss summed in bfloat16 lands on that format's
    grid, 10.25 for every seed, and says nothing of the blocks."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    bias = init[1]["expert_bias"].astype(dtype)
    rows, t = batch.shape
    fn = jax.jit(lambda p, b, tok: nll_sum(p, b, tok, model, mutate)[0])
    total = 0.0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(rows):
            total += float(fn(params, bias, batch[i]))
    return total / (rows * (t - 1))
