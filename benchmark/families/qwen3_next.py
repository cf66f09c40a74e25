"""Family ``qwen3_next``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — three layers in four a Gated DeltaNet
linear-attention mixer (the gated delta rule of ``ops/gated_delta.py``
in chunks of 64: 16 key heads and 32 value heads of 128 behind a 4-tap
depthwise convolution, a norm and a SiLU gate a head), the fourth gated
softmax attention (16 query / 2 key-value heads of 256, head norms,
rotary on a quarter of a head, an elementwise sigmoid gate from the
query projection's second half), norms of the form (1 + w), and on
every layer top-k routed gated-SiLU experts without dropped tokens over
the HELD share of the experts (softmax over the chosen) beside one
shared expert under a sigmoid gate a token, an untied head over a slice
of the vocabulary — trained on next-token cross-entropy over one
repeated batch of seeded random tokens drawn from the slice.

Configuration keys are the source's (``config.json`` of
Qwen3-Next-80B-A3B-Instruct); ``num_experts`` counts the experts held
here, ``router_outputs`` all of them, ``held_experts_first`` the first
one held. Workload keys: ``batch`` (sequences a step), ``seq`` (tokens a
sequence). The step is registered in the operator's stateful form: the
state is the routing, gate and delta-rule counters
(``decoder.counters_init``)."""

from __future__ import annotations

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.families.smallthinker import mean_keys
from benchmark.manifest import ManifestError


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before it had the delta mixer: said before any runtime starts
    (run.py exits 3 on it)."""
    from ray_tpu.models import decoder

    if "delta" not in decoder.MIXER_KINDS:
        raise ManifestError("this checkout's ray_tpu.models.decoder has no "
                            "mixer kind \"delta\": it cannot build the "
                            "qwen3_next family")
    return decoder


_decoder()


def layer_kinds(model: dict) -> tuple[str, ...]:
    """The mixer of every layer, top down, from the source's
    `full_attention_interval` n: whole periods of n - 1 delta layers and
    one of full attention (the reference has its own rule, layer i is
    full where (i + 1) % n == 0: the two are compared as everything
    else is)."""
    every, layers = model["full_attention_interval"], \
        model["num_hidden_layers"]
    if every < 1 or layers % every:
        raise ValueError(f"{layers} layers are not whole intervals of "
                         f"{every}")
    return (("delta",) * (every - 1) + ("full",)) * (layers // every)


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    if model["tie_word_embeddings"] or model["decoder_sparse_step"] != 1 \
            or model["mlp_only_layers"] or model["use_sliding_window"] \
            or model["rope_scaling"] is not None \
            or not model["norm_topk_prob"] or model["hidden_act"] != "silu" \
            or model["shared_expert_intermediate_size"] <= 0:
        raise ValueError(
            "the qwen3_next family: an untied head, every layer sparse, no "
            "window, plain rotary, routing weights renormalised over the "
            "chosen, SiLU, a shared expert")
    kinds = layer_kinds(model)
    every = model["full_attention_interval"]
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=len(kinds),
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        attention=kinds[:every], mlp=("experts",) * every,
        window=0, rotary=(), rope_theta=0.0, qk_norm=("full",),
        by_kind=(("full", decoder.AttentionKind(
            n_heads=model["num_attention_heads"],
            rope_theta=float(model["rope_theta"]),
            rope_dim=int(model["head_dim"]
                         * model["partial_rotary_factor"]))),),
        attn_gate="element", norm_plus_one=True,
        shared_gate=True, conv_taps=model["linear_conv_kernel_dim"],
        delta_key_heads=model["linear_num_key_heads"],
        delta_value_heads=model["linear_num_value_heads"],
        delta_key_dim=model["linear_key_head_dim"],
        delta_value_dim=model["linear_value_head_dim"],
        n_experts=model["router_outputs"],
        top_k=model["num_experts_per_tok"],
        d_expert=model["moe_intermediate_size"],
        d_shared=model["shared_expert_intermediate_size"],
        held=(model["held_experts_first"], model["num_experts"]),
        router_input="mlp", routing="softmax_topk", activation="silu",
        tied_head=False, count_rows=True, rms_eps=model["rms_norm_eps"],
        init_std=model["init_std"],
        dtype=getattr(jnp, model["compute_dtype"]), remat=model["remat"],
        **extra)


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax

    decoder = _decoder()
    cfg = model_cfg(model)
    batch, seq = workload["batch"], workload["seq"]
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"seq {seq} > max_position_embeddings")
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size)

    def loss_fn(p, s, b):
        return decoder.stateful_loss(p, s, b, cfg)

    # what `train.dispatch` carries of a step: the delta layers, the
    # chunks their rule walks, its heads, the attention's heads and
    # turned width
    loss_fn.step_facts = lambda b: decoder.step_facts(cfg, b.shape)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.counters_init(cfg))),
        loss_fn=loss_fn, optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def moe_layers(model: dict) -> int:
    """The layers that route: all of them."""
    return model["num_hidden_layers"]


def _delta_sizes(model: dict):
    from ray_tpu.ops.gated_delta import CHUNK

    return (model["linear_num_key_heads"], model["linear_num_value_heads"],
            model["linear_key_head_dim"], model["linear_value_head_dim"],
            CHUNK)


def delta_rule_flops_per_token(model: dict) -> dict:
    """The matrix products ONE delta layer's rule multiplies a token, as
    `ops/gated_delta.py` forms them, `{"fwd": .., "bwd": ..}`. Forward,
    a value head: K S, Q S and the state's update (3 x 2 K V), Tm R and
    tril(Q K^T o D) V' (2 x 2 C V) and the inverse at what its doubling
    multiplies (`inverse_products(C)` products of [C, C]: 2 C^3 each a
    chunk, so 2 C^2 a token); a key head: K K^T and Q K^T (2 x 2 C K).
    Backward, what it multiplies beyond the forward's own values again:
    a value head six products with the state's shape (6 x 2 K V: dO S^T,
    dKS S^T, V' dS^T, (w o K) dS, Q^T dO, K^T dKS) and four with the
    chunk's (4 x 2 C V: P^T dO, dO V'^T, Tm^T dV', dR V'^T), a key head
    four (4 x 2 C K: dQK K, dQK^T Q, dKK K, dKK^T K)."""
    from ray_tpu.ops.gated_delta import inverse_products

    groups, heads, dk, dv, c = _delta_sizes(model)
    return {
        "fwd": heads * (6 * dk * dv + 4 * c * dv
                        + inverse_products(c) * 2 * c * c)
        + groups * 4 * c * dk,
        "bwd": heads * (12 * dk * dv + 8 * c * dv) + groups * 8 * c * dk}


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part: the matrix products only.
    The delta layers' projections (W_qkvz, W_ba, W_out) and the 4-tap
    convolution; their rule's chunk products
    (`delta_rule_flops_per_token`); the attention layer's projections
    (the doubled query, k, v, W_o) and its scores INSIDE the causal mask,
    4 x 256 a score and query head; the routed experts at their
    expectation under uniform routing, top_k x held / outputs experts a
    token (0.625 of a token's ten), and said so; the shared expert, its
    gate and the router on every layer; the vocabulary is the slice's.
    Norms, rotary, softmax, sigmoids and the embedding lookup are not
    counted."""
    d, hd = model["hidden_size"], model["head_dim"]
    heads, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    groups, h, dk, dv, _ = _delta_sizes(model)
    kinds = layer_kinds(model)
    n_delta, n_full = kinds.count("delta"), kinds.count("full")
    keys, values = groups * dk, h * dv
    expert = 2 * 3 * d * model["moe_intermediate_size"]
    held_share = (model["num_experts_per_tok"] * model["num_experts"]
                  / model["router_outputs"])
    return {
        "delta_projections": n_delta * (
            2 * d * (2 * keys + 2 * values + 2 * h) + 2 * values * d
            + 2 * model["linear_conv_kernel_dim"] * (2 * keys + values)),
        "delta_rule": n_delta * delta_rule_flops_per_token(model)["fwd"],
        "attention_projections": n_full * 2 * d * (
            3 * heads * hd + 2 * n_kv * hd),
        "attention_scores": n_full * 4 * heads * hd * mean_keys(seq, None),
        "shared_experts": moe_layers(model) * (
            2 * 3 * d * model["shared_expert_intermediate_size"] + 2 * d),
        "routed_experts": moe_layers(model) * (
            held_share * expert + 2 * d * model["router_outputs"]),
        "vocabulary": 2 * d * model["vocab_size"]}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward), recomputation not counted; the vocabulary for the seq - 1
    positions that have a target."""
    seq = workload["seq"]
    part = forward_flops_per_token(model, seq)
    vocabulary = part.pop("vocabulary")
    return 3.0 * (seq * sum(part.values()) + (seq - 1) * vocabulary)


def gated_delta_flops_bytes(model: dict, workload: dict, steps: int,
                            chunks: int | None = None,
                            itemsize: int = 2) -> dict:
    """What the delta rule's kernels need for `steps` steps, the three
    layers' calls together: `{"fwd": (FLOPs, bytes), "bwd": (FLOPs,
    bytes)}`. `chunks`: the chunks a step walks (layers x sequences x T /
    C: `delta_chunks` on the traced call's `train.dispatch` span;
    default: the workload's). `gdr_fwd` runs twice a layer and step (the
    forward pass and its rematerialised copy), `gdr_bwd` once. FLOPs
    from `delta_rule_flops_per_token` (the inverse at what the kernel's
    doubling multiplies; what the backward recomputes is not counted).
    Bytes, each array once a call: forward q, k (16 heads), v, o (32
    heads) in the compute dtype, the running sums twice (rows and
    columns) and beta in float32, and the chunks' entering states `[32,
    128, 128]` float32 a chunk on BOTH calls (under `jax.checkpoint` the
    first forward is traced with the rule's differentiated form too and
    its kernel keeps the output nobody reads: the step's compiled text
    shows six `gdr_fwd` calls with it); backward q, k, v,
    do and dq, dk, dv, the sums, beta and their three gradients, and the
    entering states read once."""
    groups, h, dk, dv, c = _delta_sizes(model)
    if chunks is None:
        chunks = layer_kinds(model).count("delta") * workload["batch"] \
            * (workload["seq"] // c)
    tokens = chunks * c * steps               # layer-tokens the rule walks
    per = delta_rule_flops_per_token(model)
    acts = itemsize * (2 * groups * dk + 2 * h * dv)      # q, k, v, o
    sums = 3 * 4 * h
    state = chunks * steps * h * dk * dv * 4
    return {
        "fwd": (2.0 * tokens * per["fwd"],
                2.0 * (tokens * (acts + sums) + state)),
        "bwd": (1.0 * tokens * per["bwd"],
                tokens * (2 * acts + 2 * sums) + state)}


def attention_flops_bytes(model: dict, workload: dict, steps: int,
                          itemsize: int = 2) -> dict:
    """What the attention kernels' calls of `steps` steps need: `{"fwd":
    (FLOPs, bytes), "bwd": (FLOPs, bytes)}`. `flash_fwd` runs twice a
    full layer and step (the forward pass and its rematerialised copy),
    `flash_bwd_fused` once. FLOPs are the products INSIDE the causal
    mask, T (T + 1) / 2 scores a head and sequence: forward 4 x 256 a
    score (q k^T, p v), backward 10 x 256 (k q^T, v do^T, p^T do, ds^T
    q, k^T ds); a kernel that walks tiles outside the mask reads low.
    Bytes, each array once a call: forward q and o with the 16 query
    heads, k and v with the 2 key/value heads, and the float32 row
    log-sum-exp (counted on both calls; only the one under a gradient
    writes it); backward q, do, dq with the query heads, k, v, dk, dv
    with the key/value heads, lse and delta."""
    b, t, hd = workload["batch"], workload["seq"], model["head_dim"]
    h, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    layers = layer_kinds(model).count("full")
    scores = layers * b * h * t * mean_keys(t, None)
    rows = layers * b * t * steps
    return {
        "fwd": (2 * steps * scores * 4 * hd,
                2 * rows * ((2 * h + 2 * n_kv) * hd * itemsize + 4 * h)),
        "bwd": (steps * scores * 10 * hd,
                rows * ((3 * h + 4 * n_kv) * hd * itemsize + 8 * h))}


def expert_matmul_flops_bytes(model: dict, rows: float, layer_steps: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """What the grouped expert matmuls of `layer_steps` layer-steps need
    when `rows` assignments in all were really multiplied (the traced
    call's `moe_assignments_held`: padding not counted), forward, the
    rematerialised forward and the two backward products of each of the
    two grouped matmuls (gate|up: 2048 -> 2 x 512, down: 512 -> 2048): 4
    passes of 2 * rows * 3 D F operations. Bytes: every pass reads its
    rows in and writes them out once, and reads (the weight-gradient
    pass: writes, in float32) the 32 held experts' weights once a
    layer-step."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    flops = 4 * 2.0 * rows * 3 * d * f
    row_bytes = itemsize * rows * ((d + 2 * f) + (f + d))
    weights = model["num_experts"] * 3 * d * f * layer_steps
    return flops, 4 * row_bytes + (3 * itemsize + 4) * weights
