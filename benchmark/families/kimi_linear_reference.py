"""Plain reference of the ``kimi_linear`` family: forward pass and
next-token cross-entropy in ``jax.numpy``, float32, matmul precision
"highest". No kernel, no chunk, no inverse, no sort, no remat of a
block: a Python loop over the layers, the delta rule walked POSITION BY
POSITION, attention as a masked softmax in blocks of heads and queries,
the held experts as dense products with a dense mask of who chose them.
Nothing is imported from ``ray_tpu``; gradients are ``jax.grad`` of
this.

Written from the published configuration of
moonshotai/Kimi-Linear-48B-A3B-Instruct (``config.json``, ``model_type``
``kimi_linear``) and the Kimi Linear report (arXiv:2510.26692, section
3). With h the residual stream ``[T, 2304]`` of one sequence:

    N(x) = x / sqrt(mean(x^2) + 1e-5) * w              weight-only RMSNorm
    every layer:  h1 = h + mixer(N1(h));  h' = h1 + mlp(N2(h1))
    layer l (from 1) is latent attention where l is in
    linear_attn_config.full_attn_layers, KDA where it is in .kda_layers;
    its MLP dense where l <= first_k_dense_replace, else the expert block.

Kimi Delta Attention (32 heads, key and value 128, conv of 4 taps), x = N1(h):

    [q | k | v] = x W_in          [2304, 3 x 4096];  conv4 then SiLU over it:
                                  depthwise, causal, no bias, out_t = sum_j tap_j in_(t - 3 + j)
    q, k <- z * rsqrt(sum(z^2) + 1e-6) a head;  q <- q / sqrt(128)
    [f | u] = x W_down            [2304, 2 x 128]: the two low ranks
    g = -exp(A_log)[head] * softplus(f W_f + dt_bias)          [T, 32, 128], a CHANNEL
    beta = sigmoid(x W_beta^T)                                 [T, 32]
    per head, S [128, 128] from zero:
        S'  = Diag(exp(g_t)) S_(t-1)      row c of the state times exp(g_t[c])
        S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;   o_t = S_t^T q_t
    mixer = ((o / sqrt(mean(o^2) + 1e-5) * w_n) * sigmoid(u W_g)) W_out
                                  a head: the norm BEFORE the gate

Latent attention (32 heads; no query rank, NOTHING rotated):

    q = x W_q                     -> 32 heads of 192
    [c_kv 512 | k_s 64] = x W_kva;   c_kv = N_512(c_kv)
    c_kv W_kvb                    -> 32 heads of [k_nope 128 | v 128]
    k = [k_nope | k_s]            k_s ONE for all heads, as it came
    a = softmax(q k^T / sqrt(192) + causal) v;   mixer = a W_o

MLPs (gated SiLU): layer 1 W_2 (silu(W_1 y) * (W_3 y)) at 9216; later

    s   = sigmoid(y W_r)          float32, all 256 experts
    S   = top8(s + b)             b: the layer's selection bias, choice only
    p_e = 2.446 * s_e / (sum_{S} s + 1e-6)            the UNBIASED s
    m   = sum over e in S AND e in Held of p_e E_e(y)  +  Shared(y)     width 1024

    logits = N_f(h_L) W_head;  loss = mean next-token cross-entropy over the slice

after the loss, once a step (``bias_update``; arXiv:2408.15664):
    b_e <- b_e + u sign(mean_e' n_e' - n_e)

Departures and choices, each under ``assumed`` in the configuration's
file: ``Held`` = the experts the configuration holds (0..7 of 256, rank
0 of the thirty-two chips that share a layer) — what the others would
add is left out here as in the program, and that partial result goes on
to the next layer; the vocabulary is the slice held; the routing's 1e-6;
the bias rule and its rate; the two low ranks (128). The delta rule is
walked in blocks of ``RULE_BLOCK`` positions, and a block of attention
scores is formed, under ``jax.checkpoint`` — the same values; under
``jax.grad`` 8192 states of ``[32, 128, 128]`` a layer and 8 GB of
softmax would otherwise be kept. The parameter tree is the program's:
block leaves stacked over the layers that have them (the norms over all;
``kda_*`` over the KDA layers; ``wq_latent``, ``wkv_a``, ``kv_a_norm``,
``wkv_b``, ``wo_latent`` over the latent ones; ``w1``, ``w3``, ``w2``
the dense layer's; the router, experts and ``ws_*`` over the expert
layers); ``kda_in``'s columns are ``[q | k | v]``, ``kda_down``'s ``[f
| u]``, ``kda_beta`` is kept as ROWS, ``[32, 2304]``.

``MUTATIONS`` are alternatives the configuration did NOT take; the first
five are the issue's controls, and the tests show the comparison tells
each apart."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROUTING_EPS = 1e-6
L2_EPS = 1e-6
QUERY_BLOCK = 4096
HEAD_BLOCK = 8
RULE_BLOCK = 64

MUTATIONS = (
    "decay averaged over a head's channels",
    "state and exponentials in bfloat16", "output gate left out",
    "shared dimensions rotated", "factor 1",
    "gate before the norm", "beta dropped",
    "delta term reads the undecayed state", "q unscaled")


def _norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def delta_rule(q, k, v, g, beta, mutate: str = ""):
    """q, k, g: [T, H, K]; v: [T, H, V]; beta: [T, H] -> o [T, H, V],
    the state from zero, one position at a time."""
    low = mutate == "state and exponentials in bfloat16"
    kind = jnp.bfloat16 if low else q.dtype

    def step(state, part):
        q_t, k_t, v_t, g_t, b_t = part
        decayed = (jnp.exp(g_t.astype(kind))[:, :, None]
                   * state).astype(kind)
        read = jnp.einsum(
            "hkv,hk->hv", state if mutate
            == "delta term reads the undecayed state" else decayed,
            k_t.astype(kind))
        state = (decayed + jnp.einsum(
            "hk,hv->hkv", k_t, b_t[:, None] * (v_t - read))).astype(kind)
        return state, jnp.einsum("hkv,hk->hv", state, q_t.astype(kind))

    @jax.checkpoint
    def block(state, parts):
        return jax.lax.scan(step, state, parts)

    t, heads, dk = q.shape
    size = math.gcd(t, RULE_BLOCK)
    _, o = jax.lax.scan(
        block, jnp.zeros((heads, dk, v.shape[2]), kind),
        tuple(z.reshape(t // size, size, *z.shape[1:])
              for z in (q, k, v, g, beta)))
    return o.reshape(t, heads, -1).astype(q.dtype)


def kda_mixer(x, p, model: dict, mutate: str = ""):
    """The KDA mixer's part of the residual on the first norm's output x
    [T, D]."""
    t = x.shape[0]
    cfg = model["linear_attn_config"]
    heads, dk, taps = cfg["num_heads"], cfg["head_dim"], \
        cfg["short_conv_kernel_size"]
    keys = heads * dk
    mixed = x @ p["kda_in"]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, mixed.shape[1]), mixed.dtype), mixed])
    mixed = jax.nn.silu(sum(p["kda_conv"][j] * padded[j:j + t]
                            for j in range(taps)))
    q, k, v = (mixed[:, i * keys:(i + 1) * keys].reshape(t, heads, dk)
               for i in range(3))
    q, k = (z * jax.lax.rsqrt((z * z).sum(-1, keepdims=True) + L2_EPS)
            for z in (q, k))
    if mutate != "q unscaled":
        q = q / math.sqrt(dk)
    low = x @ p["kda_down"]
    rank = low.shape[1] // 2
    g = -jnp.exp(p["kda_A_log"])[:, None] * jax.nn.softplus(
        low[:, :rank] @ p["kda_f_up"] + p["kda_dt_bias"]).reshape(
            t, heads, dk)
    if mutate == "decay averaged over a head's channels":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(x @ p["kda_beta"].T)
    if mutate == "beta dropped":
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, g.astype(x.dtype), beta.astype(x.dtype), mutate)
    gate = jax.nn.sigmoid(low[:, rank:] @ p["kda_g_up"]).reshape(
        t, heads, dk)
    eps = model["rms_norm_eps"]
    if mutate == "output gate left out":
        o = _norm(o, p["kda_norm"], eps)
    elif mutate == "gate before the norm":
        o = _norm(o * gate, p["kda_norm"], eps)
    else:
        o = _norm(o, p["kda_norm"], eps) * gate
    return o.reshape(t, keys) @ p["kda_out"]


def _rope(x, theta: float):
    """The control's turn. x: [T, H, dim]; interleaved pairing:
    dimensions 2i and 2i + 1 turn by position * theta ** (-2i / dim)."""
    t, _, dim = x.shape
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


@jax.checkpoint
def _attention_block(qb, k, v, lo, scale):
    """Queries [lo, lo + len(qb)) of a block of heads against all T keys
    under a dense causal mask. qb: [Tq, h, d_qk]; k: [T, h, d_qk]; v:
    [T, h, d_v]."""
    i = (lo + jnp.arange(qb.shape[0]))[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
    s = jnp.where((i >= j)[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)


def _attention(q, k, v, scale: float):
    """Causal attention, q and k [T, H, d_qk], v [T, H, d_v] -> [T, H,
    d_v], a block of heads and of queries at a time."""
    t, h, _ = q.shape
    return jnp.concatenate([jnp.concatenate([
        _attention_block(q[lo:lo + QUERY_BLOCK, g:g + HEAD_BLOCK],
                         k[:, g:g + HEAD_BLOCK], v[:, g:g + HEAD_BLOCK],
                         lo, scale)
        for lo in range(0, t, QUERY_BLOCK)], axis=0)
        for g in range(0, h, HEAD_BLOCK)], axis=1)


def latent_mixer(x, p, model: dict, mutate: str = ""):
    """The latent attention's part of the residual on the first norm's
    output x [T, D]: no query rank, nothing rotated."""
    t = x.shape[0]
    h, nope = model["num_attention_heads"], model["qk_nope_head_dim"]
    rot, dv = model["qk_rope_head_dim"], model["v_head_dim"]
    r_kv = model["kv_lora_rank"]
    q = (x @ p["wq_latent"]).reshape(t, h, nope + rot)
    kv_a = x @ p["wkv_a"]
    c_kv = _norm(kv_a[:, :r_kv], p["kv_a_norm"], model["rms_norm_eps"])
    k_s = kv_a[:, r_kv:][:, None, :]                        # [T, 1, rot]
    kv = (c_kv @ p["wkv_b"]).reshape(t, h, nope + dv)
    if mutate == "shared dimensions rotated":
        theta = float(model["rope_theta"])
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
        k_s = _rope(k_s, theta)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_s, (t, h, rot))], -1)
    a = _attention(q, k, kv[..., nope:], (nope + rot) ** -0.5)
    return a.reshape(t, h * dv) @ p["wo_latent"]


def _gated(y, w1, w3, w2):
    return (jax.nn.silu(y @ w1) * (y @ w3)) @ w2


def routed(y, r, p, bias, *, first: int, k_active: int, factor: float):
    """The routed experts' part of a layer, and who was chosen. y: [T, D]
    (the MLP's input); r: [T, 256] the router's product over ALL
    experts; bias: [256]; p holds the held experts' weights, expert e of
    them being expert `first + e` of the router. Returns (m [T, D],
    n [256]: the assignments each of all experts got)."""
    s = jax.nn.sigmoid(r)
    _, chosen = jax.lax.top_k(s + bias, k_active)              # [T, 8]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = factor * picked / (picked.sum(-1, keepdims=True) + ROUTING_EPS)
    held = first + jnp.arange(p["w_gate"].shape[0])
    # p_e of every token for every held expert: 0 where it was not chosen
    p_e = (weight[:, :, None] * (chosen[:, :, None] == held)).sum(1)  # [T, E]
    act = jax.nn.silu(jnp.einsum("td,edf->etf", y, p["w_gate"])) \
        * jnp.einsum("td,edf->etf", y, p["w_up"])
    m = jnp.einsum("etf,efd->td", p_e.T[:, :, None] * act, p["w_down"])
    n = (chosen[:, :, None] == jnp.arange(r.shape[-1])).sum((0, 1))
    return m, n


def layer(h, p, bias, *, mixer: str, mlp: str, model: dict,
          mutate: str = "", first: int | None = None):
    """One block on one sequence. h: [T, D]; p: the layer's leaves; bias:
    the layer's [256], or None in a dense layer; `first`: the first
    expert held (the configuration's, unless a share test says another).
    Returns (h', the routed part alone, n or None)."""
    eps = model["rms_norm_eps"]
    mix = kda_mixer if mixer == "kda" else latent_mixer
    h1 = h + mix(_norm(h, p["norm1"], eps), p, model, mutate)
    y = _norm(h1, p["norm2"], eps)
    if mlp == "dense":
        return h1 + _gated(y, p["w1"], p["w3"], p["w2"]), None, None
    m, n = routed(
        y, y @ p["router"], p, bias,
        first=model["held_experts_first"] if first is None else first,
        k_active=model["num_experts_per_token"],
        factor=1.0 if mutate == "factor 1"
        else model["routed_scaling_factor"])
    return h1 + m + _gated(y, p["ws_gate"], p["ws_up"], p["ws_down"]), m, n


def kinds(model: dict) -> list[tuple[str, str, dict]]:
    """(mixer, mlp, {leaf group: the layer's row in that group's
    stacks}) of every layer run, from the source's own lists (layers
    numbered from 1)."""
    attn = model["linear_attn_config"]
    out, seen = [], {}
    for l in range(1, model["num_hidden_layers"] + 1):
        if l in attn["full_attn_layers"]:
            mixer = "latent"
        elif l in attn["kda_layers"]:
            mixer = "kda"
        else:
            raise ValueError(f"layer {l} is in neither list of "
                             "linear_attn_config")
        mlp = "dense" if l <= model["first_k_dense_replace"] else "experts"
        row = {"layer": l - 1}
        for group in (mixer, mlp):
            row[group] = seen.get(group, 0)
            seen[group] = row[group] + 1
        out.append((mixer, mlp, row))
    return out


def group_of(name: str) -> str:
    """The leaf group (whose layers stack the leaf) of a block leaf."""
    if name.startswith("kda_"):
        return "kda"
    if name in ("norm1", "norm2"):
        return "layer"
    if name in ("w1", "w2", "w3"):
        return "dense"
    if name in ("wq_latent", "wkv_a", "kv_a_norm", "wkv_b", "wo_latent"):
        return "latent"
    return "experts"


def layer_leaves(params, row: dict) -> dict:
    """A layer's row of every stack it has a row in."""
    return {name: leaf[row[group_of(name)]]
            for name, leaf in params["layers"].items()
            if group_of(name) in row}


def forward(params, bias, tokens, model, mutate: str = ""):
    """ONE sequence. tokens: [T]; bias: [expert layers, 256]. Returns
    (logits [T, V], n [expert layers, 256])."""
    h = params["embed"][tokens]
    counts = []
    for mixer, mlp, row in kinds(model):
        h, _, n = layer(
            h, layer_leaves(params, row),
            bias[row["experts"]] if mlp == "experts" else None,
            mixer=mixer, mlp=mlp, model=model, mutate=mutate)
        counts += [] if n is None else [n]
    return _norm(h, params["norm_f"], model["rms_norm_eps"]) \
        @ params["head"], jnp.stack(counts)


def nll_sum(params, bias, tokens, model, mutate: str = ""):
    """(summed next-token loss, n) of ONE sequence. tokens: [T]. The
    loss's own softmax and sum are float32 whatever the blocks compute
    in."""
    logits, n = forward(params, bias, tokens, model, mutate)
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).sum(), n


def bias_update(bias, n, rate: float):
    """The loss-free balancing rule. bias, n: [expert layers, 256]; n
    the assignments every expert got from the whole batch."""
    n = n.astype(jnp.float32)
    return bias + rate * jnp.sign(n.mean(-1, keepdims=True) - n)


def loss_terms(init, batch, model: dict, dtype=jnp.float32,
               mutate: str = ""):
    """(the mean next-token loss of the whole batch, n summed over its
    sequences), one sequence at a time. `init` is what the family's
    `model_init` returns: (parameters, the model state, whose
    `expert_bias` is read)."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    bias = init[1]["expert_bias"].astype(dtype)
    rows, t = batch.shape
    fn = jax.jit(lambda p, b, tok: nll_sum(p, b, tok, model, mutate))
    total, n = 0.0, 0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(rows):
            nll, n_i = fn(params, bias, batch[i])
            total, n = total + float(nll), n + n_i
    return total / (rows * (t - 1)), n


def loss(init, batch, model: dict, dtype=jnp.float32,
         mutate: str = "") -> float:
    """Mean next-token loss of the whole batch. `dtype` other than
    float32 is the precision control: the BLOCKS in `dtype` (weights,
    activations, gates, router, the delta rule's state, the attention's
    softmax), the loss's own softmax and sums still float32."""
    return loss_terms(init, batch, model, dtype, mutate)[0]
