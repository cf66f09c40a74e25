"""Family ``smallthinker``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — per layer full attention without positions
or window attention with rotary ones, grouped-query heads, RMSNorm,
top-k routed gated-ReLU experts without dropped tokens over the HELD
share of the experts, an untied head over a slice of the vocabulary —
trained on next-token cross-entropy over one repeated batch of seeded
random tokens drawn from the slice.

Configuration keys are the source's (``config.json`` of SmallThinker);
``moe_num_primary_experts`` counts the experts held here,
``router_outputs`` all of them, ``held_experts_first`` the first one
held. Workload keys: ``batch`` (sequences a step), ``seq`` (tokens a
sequence). The step is registered in the operator's stateful form: the
state is the routing counters (``decoder.counters_init``)."""

from __future__ import annotations

import importlib.util

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.manifest import ManifestError

if importlib.util.find_spec("ray_tpu.models.decoder") is None:
    # a checkout from before the pattern decoder: say so before any
    # runtime starts (run.py exits 3 on a ManifestError)
    raise ManifestError("this checkout's program has no "
                        "ray_tpu.models.decoder: it cannot build the "
                        "smallthinker family")

_KINDS = {0: "full", 1: "window"}


def model_cfg(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    layers = model["num_hidden_layers"]
    window = model["sliding_window_layout"][:layers]
    if model["rope_layout"][:layers] != window:
        raise ValueError("the decoder gives rotary positions to exactly "
                         "the window layers")
    if model["tie_word_embeddings"] or model["rope_scaling"] is not None \
            or not (model["norm_topk_prob"]
                    and model["moe_primary_router_apply_softmax"]):
        raise ValueError("the smallthinker family: untied head, unscaled "
                         "rotary, softmax over the chosen experts")
    # one period of the pattern: the shortest prefix that repeats
    period = next(n for n in range(1, layers + 1) if layers % n == 0
                  and window == window[:n] * (layers // n))
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=layers,
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        attention=tuple(_KINDS[k] for k in window[:period]),
        mlp=("experts",) * period, window=model["sliding_window_size"],
        rope_theta=float(model["rope_theta"]),
        n_experts=model["router_outputs"],
        top_k=model["moe_num_active_primary_experts"],
        d_expert=model["moe_ffn_hidden_size"],
        held=(model["held_experts_first"], model["moe_num_primary_experts"]),
        rms_eps=model["rms_norm_eps"], init_std=model["init_std"],
        dtype=getattr(jnp, model["compute_dtype"]), remat=model["remat"],
        **extra)


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax

    from ray_tpu.models import decoder

    cfg = model_cfg(model)
    batch, seq = workload["batch"], workload["seq"]
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"seq {seq} > max_position_embeddings")
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.counters_init(cfg))),
        loss_fn=lambda p, s, b: decoder.stateful_loss(p, s, b, cfg),
        optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def mean_keys(seq: int, window: int | None) -> float:
    """Keys a query meets inside its mask, averaged over a sequence:
    causal, and within the window where there is one."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part. Attention is counted INSIDE
    the masks (causal, and the window on window layers); the experts at
    their expectation under uniform routing, top_k x held / outputs
    experts a token (the held share of a token's six), and said so; the
    vocabulary is the slice's. Norms, rotary, softmax and the embedding
    lookup are not counted."""
    d, hd = model["hidden_size"], model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    layers = model["num_hidden_layers"]
    proj = 2 * (2 * d * n_q * hd + 2 * d * n_kv * hd) \
        + 2 * d * model["router_outputs"]
    attn = sum(
        2 * 2 * n_q * hd * mean_keys(
            seq, model["sliding_window_size"] if kind else None)
        for kind in model["sliding_window_layout"][:layers])
    held_share = (model["moe_num_active_primary_experts"]
                  * model["moe_num_primary_experts"]
                  / model["router_outputs"])
    experts = held_share * 2 * 3 * d * model["moe_ffn_hidden_size"]
    return {"projections": layers * proj, "attention": attn,
            "experts": layers * experts,
            "vocabulary": 2 * d * model["vocab_size"]}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward), recomputation not counted; the vocabulary for the seq - 1
    positions that have a target."""
    seq = workload["seq"]
    part = forward_flops_per_token(model, seq)
    forward = seq * (part["projections"] + part["attention"]
                     + part["experts"]) + (seq - 1) * part["vocabulary"]
    return 3.0 * forward


def expert_matmul_flops_bytes(model: dict, rows: float, layer_steps: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """What the grouped expert matmuls of `layer_steps` layer-steps need
    when `rows` assignments in all were really multiplied (the traced
    call's `moe_assignments_held`: padding not counted), forward, the
    rematerialised forward and the two backward products of each of the
    two grouped matmuls (gate|up: D -> 2F, down: F -> D): 4 passes of
    2 * rows * 3 D F operations. Bytes: every pass reads its rows in and
    writes them out once, and reads (the weight-gradient pass: writes,
    in float32) the held experts' weights once a layer-step."""
    d, f = model["hidden_size"], model["moe_ffn_hidden_size"]
    held = model["moe_num_primary_experts"]
    flops = 4 * 2.0 * rows * 3 * d * f
    # rows in + out per product: gate|up D + 2F, down F + D
    row_bytes = itemsize * rows * ((d + 2 * f) + (f + d))
    weights = held * 3 * d * f * layer_steps
    return flops, 4 * row_bytes + (3 * itemsize + 4) * weights


def window_attention_flops_bytes(model: dict, workload: dict,
                                 steps: int, itemsize: int = 2
                                 ) -> tuple[float, float]:
    """What the `flash_fwd` kernel's calls of `steps` steps need: the
    score and value products INSIDE causal ^ window only (2 products x 2
    x keys-in-mask x heads x head_dim a query), forward and its
    rematerialised copy, every layer; bytes: q and o with the query
    heads, k and v with the key/value heads, each once a call."""
    hd = model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    seq, layers = workload["seq"], model["num_hidden_layers"]
    tokens = workload["batch"] * seq * steps
    keys = sum(mean_keys(seq, model["sliding_window_size"] if k else None)
               for k in model["sliding_window_layout"][:layers])
    flops = 2 * tokens * 2 * 2 * n_q * hd * keys
    return flops, 2.0 * itemsize * tokens * layers * hd * (2 * n_q + 2 * n_kv)
