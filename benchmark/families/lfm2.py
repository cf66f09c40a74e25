"""Family ``lfm2``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — per layer a gated short-convolution mixer
or full grouped-query attention (rotary positions after an RMSNorm over
each head of q and k), RMSNorm, a dense gated-SiLU MLP in the leading
layers and top-k routed gated-SiLU experts without dropped tokens over
the HELD share of the experts after them (sigmoid scores, a selection
bias that is model state, weights normalised over the chosen), a tied
head over a slice of the vocabulary — trained on next-token
cross-entropy over one repeated batch of seeded random tokens drawn
from the slice.

Configuration keys are the source's (``config.json`` of LFM2-8B-A1B);
``num_experts`` counts the experts held here, ``router_outputs`` all of
them, ``held_experts_first`` the first one held. Workload keys:
``batch`` (sequences a step), ``seq`` (tokens a sequence). The step is
registered in the operator's stateful form: the state is the routing
counters and the selection bias (``decoder.state_init``)."""

from __future__ import annotations

import importlib.util

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.manifest import ManifestError

if importlib.util.find_spec("ray_tpu.ops.short_conv") is None:
    # a checkout from before the conv mixer: say so before any runtime
    # starts (run.py exits 3 on a ManifestError)
    raise ManifestError("this checkout's program has no "
                        "ray_tpu.ops.short_conv (the decoder's `conv` "
                        "mixer): it cannot build the lfm2 family")

_MIXER = {"conv": "conv", "full_attention": "full"}


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of every layer run, in the decoder's names."""
    layers, dense = model["num_hidden_layers"], model["num_dense_layers"]
    return [(_MIXER[kind], "dense" if l < dense else "experts")
            for l, kind in enumerate(model["layer_types"][:layers])]


def head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def model_cfg(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    if model["conv_bias"] or not (model["norm_topk_prob"]
                                  and model["use_expert_bias"]) \
            or model["routed_scaling_factor"] != 1:
        raise ValueError("the lfm2 family: no bias in the convolution, "
                         "weights normalised over the chosen experts, a "
                         "selection bias, scaling factor 1")
    kinds, lead = layer_kinds(model), model["num_dense_layers"]
    body = kinds[lead:]
    # one period of the pattern: the shortest prefix that repeats
    period = next(n for n in range(1, len(body) + 1) if len(body) % n == 0
                  and body == body[:n] * (len(body) // n))
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=len(kinds),
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=head_dim(model),
        lead_attention=tuple(a for a, _ in kinds[:lead]),
        lead_mlp=tuple(m for _, m in kinds[:lead]),
        attention=tuple(a for a, _ in body[:period]),
        mlp=tuple(m for _, m in body[:period]),
        window=0, rotary=("full",), qk_norm=("full",),
        rope_theta=float(model["rope_theta"]),
        n_experts=model["router_outputs"],
        top_k=model["num_experts_per_tok"],
        d_expert=model["moe_intermediate_size"],
        d_dense=model["intermediate_size"],
        conv_taps=model["conv_L_cache"],
        held=(model["held_experts_first"], model["num_experts"]),
        router_input="mlp", routing="sigmoid_bias",
        bias_rate=model["expert_bias_update_rate"], activation="silu",
        tied_head=True, rms_eps=model["norm_eps"],
        init_std=model["init_std"],
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
                                        decoder.state_init(key, cfg))),
        loss_fn=lambda p, s, b: decoder.stateful_loss(p, s, b, cfg),
        optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part: the matrix products only.
    Attention is counted INSIDE the causal mask; the experts at their
    expectation under uniform routing, top_k x held / outputs experts a
    token (the held QUARTER of a token's four: one), and said so; the
    vocabulary is the slice's. The convolution's and the gates'
    elementwise work (about 7 operations an element), norms, rotary,
    softmax and the embedding lookup are not counted."""
    d, hd = model["hidden_size"], head_dim(model)
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    kinds = layer_kinds(model)
    n_conv = sum(a == "conv" for a, _ in kinds)
    n_attn = len(kinds) - n_conv
    n_moe = moe_layers(model)
    held_share = (model["num_experts_per_tok"] * model["num_experts"]
                  / model["router_outputs"])
    return {
        "conv_projections": n_conv * 2 * (3 * d * d + d * d),
        "attention_projections":
            n_attn * 2 * (2 * d * n_q * hd + 2 * d * n_kv * hd),
        "attention": n_attn * 2 * 2 * n_q * hd * (seq + 1) / 2,
        "dense_mlp": (len(kinds) - n_moe) * 2 * 3 * d
        * model["intermediate_size"],
        "experts": n_moe * (held_share * 2 * 3 * d
                            * model["moe_intermediate_size"]
                            + 2 * d * model["router_outputs"]),
        "vocabulary": 2 * d * model["vocab_size"]}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward), recomputation not counted; the vocabulary for the seq - 1
    positions that have a target."""
    seq = workload["seq"]
    part = forward_flops_per_token(model, seq)
    vocabulary = part.pop("vocabulary")
    return 3.0 * (seq * sum(part.values()) + (seq - 1) * vocabulary)


def moe_layers(model: dict) -> int:
    return sum(m == "experts" for _, m in layer_kinds(model))


def expert_matmul_flops_bytes(model: dict, rows: float, layer_steps: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """What the grouped expert matmuls of `layer_steps` MoE-layer-steps
    need when `rows` assignments in all were really multiplied (the
    traced call's `moe_assignments_held`: padding not counted): the
    other expert family's reckoning (4 passes of 2 * rows * 3 D F
    operations; rows in and out once a pass, the held experts' weights
    once a layer-step) at this family's widths, D 2048, F 1792, 8 held."""
    from benchmark.families import smallthinker

    return smallthinker.expert_matmul_flops_bytes(
        {"hidden_size": model["hidden_size"],
         "moe_ffn_hidden_size": model["moe_intermediate_size"],
         "moe_num_primary_experts": model["num_experts"]},
        rows, layer_steps, itemsize)


def short_conv_flops_bytes(model: dict, workload: dict, steps: int,
                           itemsize: int = 2) -> tuple[float, float]:
    """What the `short_conv` / `short_conv_bwd` kernels' calls of `steps`
    steps need, every conv layer: the forward pass and its
    rematerialised copy each read the `[tokens, 3 D]` product and write
    `[tokens, D]`; the backward reads the product and `[tokens, D]` of
    gradient and writes `[tokens, 3 D]`: (4 + 4 + 7) D elements a token
    and layer, each array once. The taps, the partial sums of their
    gradient and a tile's 16 rows of history (under 4 % of a tile of
    512) are left out: the share reads a little low. Operations: z, K
    taps and the gate forward (2 K + 1 an element), about three times
    that backward - far below the bytes' time on this chip."""
    d, k = model["hidden_size"], model["conv_L_cache"]
    layers = sum(a == "conv" for a, _ in layer_kinds(model))
    elements = workload["batch"] * workload["seq"] * steps * layers * d
    return (2 + 3) * (2 * k + 1) * elements, 15.0 * itemsize * elements
