"""Plain reference of the ``laguna`` family: forward pass and next-token
cross-entropy in ``jax.numpy``, float32, matmul precision "highest". No
kernel, no sort, no scan, no remat: a Python loop over the layers, dense
``[T, T]`` masks, the held experts as dense products with a dense mask of
who chose them. Nothing is imported from ``ray_tpu``; gradients are
``jax.grad`` of this.

Written from the published configuration of poolside/Laguna-XS.2
(``config.json``, ``model_type`` ``laguna``) and, for the two keys that
need a reading, its sibling Laguna-S-2.1's (``gating: "per-head"``,
``norm_topk_prob: true``). With h the residual stream ``[T, 2048]``,
layer l of kind k = ``layer_types[l]`` (``full_attention`` /
``sliding_attention``), ``H_k = num_attention_heads_per_layer[l]`` (48 /
64) query heads over 8 key/value heads of 128:

    x  = RMSNorm1(h)                                   eps 1e-6, weight only
    q  = x W_q^k  [T, H_k, 128];  k, v = x W_k, x W_v  [T, 8, 128]; no bias
    q, k = RoPE_k(q), RoPE_k(k)     rotate-half over the first r_k dimensions of a head,
                                    the other 128 - r_k pass; r = 128 * partial_rotary_factor
       sliding: r = 128, rates 1e4 ** (-2i / 128)
       full:    r = 64, YaRN: d = 64, b = 5e5, s = 64, L0 = 4096
                c(n) = d ln(L0 / (2 pi n)) / (2 ln b)
                low = max(floor(c(beta_fast = 64)), 0) = 5;  high = min(ceil(c(beta_slow = 1)), d - 1) = 16
                ramp_i = clip((i - low) / (high - low), 0, 1),  i = 0 .. d/2 - 1
                rate_i = b ** (-2i / d) * (1 - ramp_i) + b ** (-2i / d) / s * ramp_i
                cos, sin *= attention_factor 1.4158883083359672
    mask_full = causal;  mask_sliding = causal AND 0 <= i - j < 512
    a  = softmax(q k^T / sqrt(128) + mask) v           query head n reads key/value head n // (H_k / 8)
    g  = sigmoid(x W_g^k)  [T, H_k]                    the per-head output gate
    h1 = h + (g * a) W_o^k
    y  = RMSNorm2(h1)
    layer 0 (mlp_layer_types "dense"):   h' = h1 + W_2 (silu(W_1 y) * (W_3 y))     width 8192
    layers 1.. ("sparse"):  r = y W_r over all 256;  S = top8(r);  w = softmax(r[S])
        m  = 2.5 * sum over e in S AND e in Held of  w_e W_down,e (silu(W_gate,e y) * (W_up,e y))   width 512
        h' = h1 + m + Ws_down (silu(Ws_gate y) * (Ws_up y))                          the shared expert, 512
    logits = RMSNorm_f(h_L) W_head;  loss = mean next-token cross-entropy over the slice

Departures and choices, each under ``assumed`` in the configuration's
file: ``Held`` = the experts the configuration holds (0..31 of 256, rank
0 of the eight chips that share a layer) — what the others would add is
left out here as in the program, and that partial result goes on to the
next layer; the vocabulary is the slice held. The parameter tree is the
program's: block leaves stacked over the layers that have them (``wk``,
``wv`` and the norms over all five; ``wq_full``, ``wo_full``,
``wg_full`` over the full layers and ``wq_window``, ``wo_window``,
``wg_window`` over the sliding ones; ``w1``, ``w3``, ``w2`` the dense
layer's; ``router``, ``w_gate``, ``w_up``, ``w_down`` (the held experts
along the next axis), ``ws_*`` the sparse layers').

``MUTATIONS`` are alternatives the configuration did NOT take; the tests
show the comparison tells each apart.

It computes in blocks so that it fits beside the training state on the
chip: one sequence at a time, one key/value head's queries
(``QUERY_BLOCK`` of them: an ``[8, 2048, 8192]`` float32 score block is
0.54 GB) at a time, the experts ``EXPERT_BLOCK`` at a time with the
routing weight applied before the down-projection so that no ``[experts,
T, 2048]`` array is formed (``[16, 8192, 512]`` is 0.27 GB), the logits
one sequence at a time (0.41 GB)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048
EXPERT_BLOCK = 16

KIND_OF = {"full_attention": "full", "sliding_attention": "window"}
MLP_OF = {"dense": "dense", "sparse": "experts"}

MUTATIONS = (
    "gate dropped", "head grouping of the kinds swapped", "ramp off",
    "cos/sin factor off", "the whole head turned on full", "thetas swapped",
    "window + 1", "window - 1", "scale dropped", "shared expert dropped",
    "softmax over all 256 without renormalising")


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rope_tables(t: int, kind: str, model: dict, mutate: str = ""):
    """(cos, sin) [T, r / 2] float32 of the kind's rotary rule, r the
    turned width."""
    rules = model["rope_parameters"]
    names = ("full_attention", "sliding_attention")
    rule = rules[names[kind == "window"]]
    theta = rules[names[(kind == "window") != (mutate == "thetas swapped")]][
        "rope_theta"]
    width = int(model["head_dim"] * rule["partial_rotary_factor"])
    if mutate == "the whole head turned on full":
        width = model["head_dim"]
    pair = jnp.arange(width // 2, dtype=jnp.float32)
    rate = theta ** (-2 * pair / width)
    scale = 1.0
    if rule["rope_type"] == "yarn":
        def pair_turning(n):   # the pair that turns n times over L0
            return width * math.log(
                rule["original_max_position_embeddings"]
                / (2 * math.pi * n)) / (2 * math.log(theta))

        low = max(math.floor(pair_turning(rule["beta_fast"])), 0)
        high = min(math.ceil(pair_turning(rule["beta_slow"])), width - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((pair - low) / (high - low), 0.0, 1.0)
        if mutate != "ramp off":
            rate = rate * (1 - ramp) + rate / rule["factor"] * ramp
        if mutate != "cos/sin factor off":
            scale = rule["attention_factor"]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * rate[None, :]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def _rope(x, cos, sin):
    """x: [T, H, hd]; the first 2 x cos's width dimensions turn,
    rotate-half within them (dimension i with i + width / 2)."""
    half = cos.shape[-1]
    cos, sin = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _attention_block(q, k, v, lo, window):
    """One key/value head's queries [lo, lo + len(q)) against all T keys
    under a dense mask. q: [Tq, group, hd]; k, v: [T, hd]."""
    i = (lo + jnp.arange(q.shape[0]))[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    mask = i >= j
    if window is not None:
        mask = mask & (i - j < window)
    s = jnp.einsum("qhd,kd->hqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1), v)


def _attention(q, k, v, window, reads):
    """q: [T, H, hd]; k, v: [T, H_kv, hd]; `reads[n]`: the key/value head
    query head n reads."""
    t = q.shape[0]
    heads = []
    for g in range(k.shape[1]):
        mine = [n for n, kv in enumerate(reads) if kv == g]
        heads.append((mine, jnp.concatenate([
            _attention_block(q[lo:lo + QUERY_BLOCK, mine[0]:mine[-1] + 1],
                             k[:, g], v[:, g], lo, window)
            for lo in range(0, t, QUERY_BLOCK)], axis=0)))
    assert [n for mine, _ in heads for n in mine] == list(range(q.shape[1]))
    return jnp.concatenate([a for _, a in heads], axis=1)      # [T, H, hd]


def mixer(x, p, kind: str, model: dict, mutate: str = ""):
    """The attention's part of the residual on the first norm's output
    x [T, D]; p holds the layer's `wq`, `wk`, `wv`, `wo`, `wg`."""
    t, hd = x.shape[0], model["head_dim"]
    n_kv = model["num_key_value_heads"]
    heads = p["wq"].shape[-1] // hd
    q = (x @ p["wq"]).reshape(t, heads, hd)
    k = (x @ p["wk"]).reshape(t, n_kv, hd)
    v = (x @ p["wv"]).reshape(t, n_kv, hd)
    tables = rope_tables(t, kind, model, mutate if kind == "full" or mutate
                         == "thetas swapped" else "")
    q, k = _rope(q, *tables), _rope(k, *tables)
    group = heads // n_kv
    if mutate == "head grouping of the kinds swapped":
        other = [n for n in set(model["num_attention_heads_per_layer"])
                 if n != heads][0]
        group = other // n_kv
    reads = [min(n // group, n_kv - 1) for n in range(heads)]
    window = None
    if kind == "window":
        window = model["sliding_window"] + {"window + 1": 1,
                                            "window - 1": -1}.get(mutate, 0)
    a = _attention(q, k, v, window, reads)
    if mutate != "gate dropped":
        a = a * jax.nn.sigmoid(x @ p["wg"])[:, :, None]
    return a.reshape(t, heads * hd) @ p["wo"]


def _gated(y, w1, w3, w2):
    return (jax.nn.silu(y @ w1) * (y @ w3)) @ w2


def routed(y, r, p, *, first: int, k_active: int, factor: float,
           mutate: str = ""):
    """The routed experts' part of a layer. y: [T, D] (the MLP's input);
    r: [T, 256] the router's product over ALL experts; p holds the held
    experts' weights, expert e of them being expert `first + e` of the
    router. Returns (m [T, D], n [256]: the assignments each of all
    experts got)."""
    top, chosen = jax.lax.top_k(r, k_active)                   # [T, 8]
    if mutate == "softmax over all 256 without renormalising":
        weight = jnp.take_along_axis(jax.nn.softmax(r, -1), chosen, -1)
    else:
        weight = jax.nn.softmax(top, axis=-1)
    weight = factor * weight
    m = jnp.zeros_like(y)
    for lo in range(0, p["w_gate"].shape[0], EXPERT_BLOCK):
        w_gate, w_up, w_down = (p[name][lo:lo + EXPERT_BLOCK]
                                for name in ("w_gate", "w_up", "w_down"))
        held = first + lo + jnp.arange(w_gate.shape[0])
        # w_e of every token for these experts: 0 where it was not chosen
        w_e = (weight[:, :, None] * (chosen[:, :, None] == held)).sum(1)
        act = jax.nn.silu(jnp.einsum("td,edf->etf", y, w_gate)) \
            * jnp.einsum("td,edf->etf", y, w_up)
        m = m + jnp.einsum("etf,efd->td", w_e.T[:, :, None] * act, w_down)
    n = (chosen[:, :, None] == jnp.arange(r.shape[-1])).sum((0, 1))
    return m, n


def layer(h, p, *, kind: str, mlp: str, model: dict, mutate: str = "",
          first: int | None = None):
    """One block on one sequence. h: [T, D]; p: the layer's leaves under
    their plain names (`wq`, `wo`, `wg` the kind's own); `first`: the
    first expert held (the configuration's, unless a share test says
    another). Returns (h', the routed part alone or None, n or None)."""
    eps = model["rms_norm_eps"]
    h1 = h + mixer(_rmsnorm(h, p["norm1"], eps), p, kind, model, mutate)
    y = _rmsnorm(h1, p["norm2"], eps)
    if mlp == "dense":
        return h1 + _gated(y, p["w1"], p["w3"], p["w2"]), None, None
    m, n = routed(
        y, y @ p["router"], p,
        first=model["held_experts_first"] if first is None else first,
        k_active=model["num_experts_per_tok"],
        factor=1.0 if mutate == "scale dropped"
        else model["moe_routed_scaling_factor"], mutate=mutate)
    shared = 0.0 if mutate == "shared expert dropped" else _gated(
        y, p["ws_gate"], p["ws_up"], p["ws_down"])
    return h1 + m + shared, m, n


_GROUP_OF = {
    "norm1": "layer", "norm2": "layer", "wk": "layer", "wv": "layer",
    "w1": "dense", "w3": "dense", "w2": "dense",
    "router": "experts", "w_gate": "experts", "w_up": "experts",
    "w_down": "experts", "ws_gate": "experts", "ws_up": "experts",
    "ws_down": "experts"}


def layer_leaves(params, l: int, model: dict) -> tuple[str, str, dict]:
    """(kind, mlp, layer l's row of every stack it has a row in, the
    kind's `wq_<kind>`, `wo_<kind>`, `wg_<kind>` under `wq`, `wo`,
    `wg`)."""
    kinds = [KIND_OF[k] for k in model["layer_types"][:l + 1]]
    mlps = [MLP_OF[m] for m in model["mlp_layer_types"][:l + 1]]
    kind, mlp = kinds[-1], mlps[-1]
    row = {"layer": l, kind: kinds[:-1].count(kind),
           mlp: mlps[:-1].count(mlp)}
    layers = params["layers"]
    p = {name: leaf[row[_GROUP_OF[name]]] for name, leaf in layers.items()
         if _GROUP_OF.get(name) in row}
    p.update({plain: layers[f"{plain}_{kind}"][row[kind]]
              for plain in ("wq", "wo", "wg")})
    return kind, mlp, p


def forward(params, tokens, model, mutate: str = ""):
    """ONE sequence. tokens: [T] -> (logits [T, V], n [sparse layers,
    256])."""
    h = params["embed"][tokens]
    counts = []
    for l in range(model["num_hidden_layers"]):
        kind, mlp, p = layer_leaves(params, l, model)
        h, _, n = layer(h, p, kind=kind, mlp=mlp, model=model, mutate=mutate)
        counts += [] if n is None else [n]
    return _rmsnorm(h, params["norm_f"], model["rms_norm_eps"]) \
        @ params["head"], jnp.stack(counts)


def nll_sum(params, tokens, model, mutate: str = ""):
    """Summed next-token loss of ONE sequence. tokens: [T]. The loss's
    own softmax and sum are float32 whatever the blocks compute in: a
    loss summed in bfloat16 lands on that format's grid (steps of 512 at
    80 000), and where it lands is the seed's luck."""
    logp = jax.nn.log_softmax(
        forward(params, tokens, model, mutate)[0][:-1].astype(jnp.float32),
        axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).sum()


def loss(init, batch, model: dict, dtype=jnp.float32,
         mutate: str = "") -> float:
    """Mean next-token loss of the whole batch, one sequence at a time.
    `init` is what the family's `model_init` returns: (parameters, the
    counters' state); only the parameters are read. `dtype` other than
    float32 is the precision control: the BLOCKS in `dtype` (weights,
    activations, rotary tables, gate, router, the attention's softmax),
    the loss's own softmax and sums still float32."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    rows, t = batch.shape
    fn = jax.jit(lambda p, tok: nll_sum(p, tok, model, mutate))
    total = 0.0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(rows):
            total += float(fn(params, batch[i]))
    return total / (rows * (t - 1))
