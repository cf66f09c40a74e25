"""Family ``laguna``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — attention layers of two kinds whose
query-head count, rotary rule and window differ by kind (full: 48 heads,
YaRN-scaled rotary on half of a head; sliding: 64 heads, plain rotary on
all of it, a window of 512; 8 key/value heads of 128 on both), a
per-head sigmoid gate on the attention's output, a dense gated-SiLU MLP
in the leading layer and after it top-k routed gated-SiLU experts without
dropped tokens over the HELD share of the experts (softmax over the
chosen logits times a scaling factor) beside one shared expert, an
untied head over a slice of the vocabulary — trained on next-token
cross-entropy over one repeated batch of seeded random tokens drawn from
the slice.

Configuration keys are the source's (``config.json`` of Laguna-XS.2);
``num_experts`` counts the experts held here, ``router_outputs`` all of
them, ``held_experts_first`` the first one held. Workload keys:
``batch`` (sequences a step), ``seq`` (tokens a sequence). The step is
registered in the operator's stateful form: the state is the routing and
gate counters (``decoder.counters_init``)."""

from __future__ import annotations

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.families.laguna_reference import KIND_OF, MLP_OF
from benchmark.families.smallthinker import mean_keys
from benchmark.manifest import ManifestError


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before attention kinds had head counts and rotary rules of their
    own: said before any runtime starts (run.py exits 3 on it)."""
    from ray_tpu.models import decoder

    if not hasattr(decoder, "AttentionKind"):
        raise ManifestError("this checkout's ray_tpu.models.decoder gives "
                            "every attention layer one head count and one "
                            "rotary rule: it cannot build the laguna family")
    return decoder


_decoder()


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of every layer run, in the decoder's names."""
    n = model["num_hidden_layers"]
    return [(KIND_OF[a], MLP_OF[m]) for a, m in zip(
        model["layer_types"][:n], model["mlp_layer_types"][:n])]


def heads_of(model: dict) -> dict:
    """attention kind -> its query heads, from the per-layer list."""
    heads = {}
    for kind, n in zip(model["layer_types"],
                       model["num_attention_heads_per_layer"]):
        if heads.setdefault(KIND_OF[kind], n) != n:
            raise ValueError("the laguna family: one head count a layer kind")
    return heads


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    if model["attention_bias"] or model["tie_word_embeddings"] \
            or model["moe_apply_router_weight_on_input"] \
            or model["gating"] is not True \
            or model["shared_expert_intermediate_size"] <= 0:
        raise ValueError(
            "the laguna family: no bias in the attention, an untied head, "
            "routing weights on the experts' outputs, a per-head output "
            "gate, a shared expert")
    kinds = layer_kinds(model)
    lead = next(i for i, (_, m) in enumerate(kinds) if m != "dense")
    rest = kinds[lead:]
    # one period of the pattern: the shortest prefix that repeats
    period = next(n for n in range(1, len(rest) + 1) if len(rest) % n == 0
                  and rest == rest[:n] * (len(rest) // n))
    by_kind, heads = [], heads_of(model)
    for source, kind in KIND_OF.items():
        if kind not in heads:
            continue
        rule = model["rope_parameters"][source]
        yarn = rule["rope_type"] == "yarn"
        if rule["rope_type"] not in ("default", "yarn"):
            raise ValueError(f"rope_type {rule['rope_type']!r} is not built")
        by_kind.append((kind, decoder.AttentionKind(
            n_heads=heads[kind], rope_theta=float(rule["rope_theta"]),
            rope_dim=int(model["head_dim"] * rule["partial_rotary_factor"]),
            yarn=(float(rule["factor"]),
                  rule["original_max_position_embeddings"],
                  float(rule["beta_fast"]), float(rule["beta_slow"]))
            if yarn else None,
            rope_scale=float(rule["attention_factor"]) if yarn else 1.0)))
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=len(kinds),
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        lead_attention=tuple(a for a, _ in kinds[:lead]),
        lead_mlp=tuple(m for _, m in kinds[:lead]),
        attention=tuple(a for a, _ in rest[:period]),
        mlp=tuple(m for _, m in rest[:period]),
        window=model["sliding_window"], rotary=(), rope_theta=0.0,
        by_kind=tuple(by_kind), attn_gate=True,
        n_experts=model["router_outputs"],
        top_k=model["num_experts_per_tok"],
        d_expert=model["moe_intermediate_size"],
        d_shared=model["shared_expert_intermediate_size"],
        routed_scale=model["moe_routed_scaling_factor"],
        d_dense=model["intermediate_size"],
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

    # what `train.dispatch` carries of a step: the kinds' head counts,
    # the window, the rotary scaling, the window layers' score entries
    # inside the mask and in the tiles the kernels walk
    loss_fn.step_facts = lambda b: decoder.step_facts(cfg, b.shape)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.counters_init(cfg))),
        loss_fn=loss_fn, optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def moe_layers(model: dict) -> int:
    """The layers that route."""
    return sum(m == "experts" for _, m in layer_kinds(model))


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part: the matrix products only.
    Attention is counted INSIDE each kind's mask (causal, and the window
    on sliding layers), 4 x 128 a score and query head; the routed
    experts at their expectation under uniform routing, top_k x held /
    outputs experts a token (one of a token's eight), and said so; the
    vocabulary is the slice's. Norms, rotary, softmax, the gate's
    sigmoid and the embedding lookup are not counted."""
    d, hd = model["hidden_size"], model["head_dim"]
    n_kv, heads = model["num_key_value_heads"], heads_of(model)
    kinds = layer_kinds(model)
    expert = 2 * 3 * d * model["moe_intermediate_size"]
    held_share = (model["num_experts_per_tok"] * model["num_experts"]
                  / model["router_outputs"])
    return {
        "projections": sum(2 * d * (2 * heads[a] * hd + 2 * n_kv * hd
                                    + heads[a]) for a, _ in kinds),
        "attention_full": sum(4 * heads[a] * hd * mean_keys(seq, None)
                              for a, _ in kinds if a == "full"),
        "attention_window": sum(
            4 * heads[a] * hd * mean_keys(seq, model["sliding_window"])
            for a, _ in kinds if a == "window"),
        "dense_mlp": sum(m == "dense" for _, m in kinds) * 2 * 3 * d
        * model["intermediate_size"],
        "shared_experts": moe_layers(model) * 2 * 3 * d
        * model["shared_expert_intermediate_size"],
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


def attention_flops_bytes(model: dict, workload: dict, steps: int,
                          itemsize: int = 2) -> dict:
    """What the attention kernels' calls of `steps` steps need, both
    kinds' calls together: `{"fwd": (FLOPs, bytes), "bwd": (FLOPs,
    bytes)}`. `flash_fwd` runs twice a layer and step (the forward pass
    and its rematerialised copy), `flash_bwd_fused` once. FLOPs are the
    products INSIDE the kind's mask — T (T + 1) / 2 scores a head and
    sequence on a full layer, T x `mean_keys(T, 512)` on a sliding one —
    forward 4 x 128 a score (q k^T, p v), backward 10 x 128 (k q^T, v
    do^T, p^T do, ds^T q, k^T ds); a kernel that walks tiles outside the
    mask reads low. Bytes, each array once a call: forward q and o with
    the kind's query heads, k and v with the 8 key/value heads, and the
    float32 row log-sum-exp (counted on both calls; only the one under a
    gradient writes it); backward q, do, dq with the query heads, k, v,
    dk, dv with the key/value heads, lse and delta."""
    b, t, hd = workload["batch"], workload["seq"], model["head_dim"]
    n_kv, heads = model["num_key_value_heads"], heads_of(model)
    fwd_flops = bwd_flops = fwd_bytes = bwd_bytes = 0.0
    for kind, _ in layer_kinds(model):
        h = heads[kind]
        scores = b * h * t * mean_keys(
            t, model["sliding_window"] if kind == "window" else None)
        rows = b * t * steps
        fwd_flops += 2 * steps * scores * 4 * hd
        bwd_flops += steps * scores * 10 * hd
        fwd_bytes += 2 * rows * ((2 * h + 2 * n_kv) * hd * itemsize + 4 * h)
        bwd_bytes += rows * ((3 * h + 4 * n_kv) * hd * itemsize + 8 * h)
    return {"fwd": (fwd_flops, fwd_bytes), "bwd": (bwd_flops, bwd_bytes)}


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
