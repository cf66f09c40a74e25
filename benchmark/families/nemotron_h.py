"""Family ``nemotron_h``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` out of blocks that are each a mixer OR a
feed-forward part alone, one RMSNorm a block — Mamba-2 state-space
mixers (``M``: one input projection, a causal convolution of 4 taps with
bias and a SiLU, the scan of ``ray_tpu/ops/ssd.py`` in chunks of 128
with a ``[64, 128]`` float32 state a head, a gate before a grouped
RMSNorm), full grouped-query attention without positions (``*``), and
top-k routed UNGATED squared-ReLU experts without dropped tokens over
the HELD share of the experts beside one shared expert (``E``: sigmoid
scores, a selection bias that is model state, weights normalised over
the chosen times a scaling factor), an untied head over a slice of the
vocabulary — trained on next-token cross-entropy over one repeated batch
of seeded random tokens drawn from the slice.

Configuration keys are the source's (``config.json`` of
NVIDIA-Nemotron-3-Nano-30B-A3B); the blocks run are the FIRST
``num_hidden_layers`` characters of ``hybrid_override_pattern``, which
stays as published; ``n_routed_experts`` counts the experts held here,
``router_outputs`` all of them, ``held_experts_first`` the first one
held. Workload keys: ``batch`` (sequences a step), ``seq`` (tokens a
sequence). The step is registered in the operator's stateful form: the
state is the routing and scan counters and the selection bias
(``decoder.state_init``)."""

from __future__ import annotations

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.manifest import ManifestError

_MIXER = {"M": "ssm", "*": "full"}
_MLP = {"E": "experts", "-": "dense"}


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before the ssm mixer: said before any runtime starts (run.py exits 3
    on it)."""
    from ray_tpu.models import decoder

    if "ssm" not in decoder.MIXER_KINDS:
        raise ManifestError("this checkout's ray_tpu.models.decoder has no "
                            "`ssm` mixer: it cannot build the nemotron_h "
                            "family")
    return decoder


_decoder()


def blocks(model: dict) -> str:
    """The blocks run, one character each."""
    return model["hybrid_override_pattern"][:model["num_hidden_layers"]]


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """The blocks read as pre-norm residual layers, (mixer, mlp) in the
    decoder's names: a mixer block and the feed-forward block after it
    are one layer, a mixer followed by a mixer is (mixer, none), a
    feed-forward block that follows one is (none, mlp). h + f(norm(h))
    block by block is the same function either way."""
    out, pattern, i = [], blocks(model), 0
    while i < len(pattern):
        if pattern[i] in _MLP:
            out.append(("none", _MLP[pattern[i]]))
        elif i + 1 < len(pattern) and pattern[i + 1] in _MLP:
            out.append((_MIXER[pattern[i]], _MLP[pattern[i + 1]]))
            i += 1
        else:
            out.append((_MIXER[pattern[i]], "none"))
        i += 1
    return out


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    if model["attention_bias"] or model["mlp_bias"] or model["use_bias"] \
            or model["mamba_proj_bias"] or not model["use_conv_bias"] \
            or not model["norm_topk_prob"] or model["n_group"] != 1 \
            or model["topk_group"] != 1 or model["tie_word_embeddings"] \
            or model["sliding_window"] is not None \
            or (model["mamba_hidden_act"], model["mlp_hidden_act"]) \
            != ("silu", "relu2"):
        raise ValueError(
            "the nemotron_h family: no bias but the convolution's, SiLU in "
            "the mixer and squared ReLU in the feed-forward parts, sigmoid "
            "scores with a selection bias and no group limit, weights "
            "normalised over the chosen, no window, an untied head")
    kinds = layer_kinds(model)
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=len(kinds),
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        attention=tuple(a for a, _ in kinds),
        mlp=tuple(m for _, m in kinds), window=0, rotary=(),
        rope_theta=float(model["rope_theta"]),
        ssm_heads=model["mamba_num_heads"],
        ssm_head_dim=model["mamba_head_dim"], ssm_groups=model["n_groups"],
        ssm_state=model["ssm_state_size"], ssm_chunk=model["chunk_size"],
        conv_taps=model["conv_kernel"],
        ssm_dt_range=(model["time_step_min"], model["time_step_max"],
                      model["time_step_floor"]),
        n_experts=model["router_outputs"],
        top_k=model["num_experts_per_tok"],
        d_expert=model["moe_intermediate_size"],
        d_shared=model["n_shared_experts"]
        * model["moe_shared_expert_intermediate_size"],
        d_dense=model["intermediate_size"],
        routed_scale=model["routed_scaling_factor"],
        held=(model["held_experts_first"], model["n_routed_experts"]),
        router_input="mlp", routing="sigmoid_bias",
        bias_rate=model["expert_bias_update_rate"],
        activation=model["mlp_hidden_act"], gated=False, tied_head=False,
        count_rows=True,
        rms_eps=model["layer_norm_epsilon"], init_std=model["init_std"],
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

    # what `train.dispatch` carries of a step: ssm_layers, ssm_chunks
    loss_fn.step_facts = lambda b: decoder.step_facts(cfg, b.shape)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.state_init(key, cfg))),
        loss_fn=loss_fn, optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def count(model: dict, kind: str) -> int:
    """The layers whose mixer or MLP is `kind`."""
    return sum(kind in pair for pair in layer_kinds(model))


def moe_layers(model: dict) -> int:
    return count(model, "experts")


def _scan_products(model: dict) -> tuple[float, float]:
    """(forward, backward) FLOPs of ONE chunk of the scan, all heads: the
    products the chunked equations name. Forward, a head: (C B^T o L)
    (dt o X) 2 Q^2 P, C S_in^T 2 Q N P, the state's update 2 Q P N; C B^T
    2 Q^2 N once a GROUP. Backward, a head, the two gradients of each of
    those four products: 2 x 2 Q^2 P, 2 x 2 Q^2 N (dG differs by head),
    4 x 2 Q P N; what the kernel recomputes (C B^T, C S_in^T) is not
    counted."""
    q, h, p = model["chunk_size"], model["mamba_num_heads"], \
        model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    forward = h * (2 * q * q * p + 4 * q * n * p) + g * 2 * q * q * n
    backward = h * (4 * q * q * p + 4 * q * q * n + 8 * q * n * p)
    return float(forward), float(backward)


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part: the matrix products only.
    Attention is counted INSIDE the causal mask; the routed experts at
    their expectation under uniform routing, top_k x held / outputs
    experts a token (6 x 8 / 128: three eighths of one), and said so;
    an expert is TWO matrices; the vocabulary is the slice's. The scan's
    products are the chunked form's (`_scan_products`); the convolution,
    gates, norms, softmax and the embedding lookup are not counted."""
    d = model["hidden_size"]
    n_q, n_kv, hd = model["num_attention_heads"], \
        model["num_key_value_heads"], model["head_dim"]
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    state = 2 * model["n_groups"] * model["ssm_state_size"]
    n_ssm, n_attn, n_moe = count(model, "ssm"), count(model, "full"), \
        moe_layers(model)
    expert = 2 * 2 * d * model["moe_intermediate_size"]
    held_share = (model["num_experts_per_tok"] * model["n_routed_experts"]
                  / model["router_outputs"])
    return {
        "ssm_projections": n_ssm * 2 * (
            d * (2 * inner + state + model["mamba_num_heads"]) + inner * d),
        "ssm_scan": n_ssm * _scan_products(model)[0] / model["chunk_size"],
        "attention_projections":
            n_attn * 2 * (2 * d * n_q * hd + 2 * d * n_kv * hd),
        "attention": n_attn * 2 * 2 * n_q * hd * (seq + 1) / 2,
        "dense_mlp": count(model, "dense") * 2 * 2 * d
        * model["intermediate_size"],
        "shared_experts": n_moe * model["n_shared_experts"] * 2 * 2 * d
        * model["moe_shared_expert_intermediate_size"],
        "routed_experts": n_moe * held_share * expert,
        "routers": n_moe * 2 * d * model["router_outputs"],
        "vocabulary": 2 * d * model["vocab_size"]}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward), recomputation not counted; the vocabulary for the seq - 1
    positions that have a target."""
    seq = workload["seq"]
    part = forward_flops_per_token(model, seq)
    vocabulary = part.pop("vocabulary")
    return 3.0 * (seq * sum(part.values()) + (seq - 1) * vocabulary)


def ssd_flops_bytes(model: dict, workload: dict, steps: int,
                    itemsize: int = 2, chunks: int | None = None) -> dict:
    """What the scan kernels' calls of `steps` steps need: `{"fwd":
    (FLOPs, bytes), "bwd": (FLOPs, bytes)}`. `chunks`: the chunks a step
    walks as the traced call's `train.dispatch` span says (`ssm_chunks`);
    from the workload's shapes where it is not given. `ssd_fwd` runs twice a
    layer and step (the forward pass and its rematerialised copy, which
    also writes each chunk's entering state), `ssd_bwd` once. FLOPs:
    `_scan_products` a chunk. Bytes, every array a pass reads or writes,
    once, a chunk of Q positions: forward x and y (Q x heads x P), B and
    C (Q x groups x N), dt and dt A as float32 rows (2 x heads x Q), and
    on the second call the states (heads x P x N float32); backward x,
    dy, dx, B, C, dB, dC, the states, four float32 rows of heads x Q
    (dt, dt A and their gradients) and D's partial sums (heads x P). The
    bytes bound both on this chip (forward 118 FLOP a byte without the
    states and 74 with them, backward 148, against the chip's 240)."""
    q, h, p = model["chunk_size"], model["mamba_num_heads"], \
        model["mamba_head_dim"]
    gn = model["n_groups"] * model["ssm_state_size"]
    if chunks is None:
        chunks = count(model, "ssm") * workload["batch"] \
            * (workload["seq"] // q)
    chunks *= steps
    forward, backward = _scan_products(model)
    rows, states = 4 * h * q, 4 * h * p * model["ssm_state_size"]
    fwd_bytes = 2 * (2 * q * h * p + 2 * q * gn) * itemsize + 2 * 2 * rows \
        + states
    bwd_bytes = (3 * q * h * p + 4 * q * gn) * itemsize + 4 * rows + states \
        + 4 * h * p
    return {"fwd": (2 * chunks * forward, chunks * fwd_bytes),
            "bwd": (chunks * backward, chunks * bwd_bytes)}


def expert_matmul_flops_bytes(model: dict, rows: float, layer_steps: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """What the grouped expert matmuls of `layer_steps` MoE-layer-steps
    need when `rows` assignments in all were really multiplied (the
    traced call's `moe_assignments_held`: padding not counted), at TWO
    products an expert (up D -> F, down F -> D; no gate): four passes —
    forward, its rematerialised copy, the gradient of the rows, the
    gradient of the weights — of 2 x rows x 2 D F operations; a pass
    reads or writes the rows in and out once a product (D + F elements
    each) and the held experts' two matrices once a layer-step (the
    weights' gradient written in float32)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    held = model["n_routed_experts"]
    flops = 4 * 2.0 * rows * 2 * d * f
    weights = layer_steps * held * 2 * d * f
    nbytes = 4 * rows * 2 * (d + f) * itemsize \
        + weights * (3 * itemsize + 4)
    return flops, nbytes
