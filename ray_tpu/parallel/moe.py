"""Expert parallelism: Switch-style top-1 MoE with all_to_all dispatch over
the `ep` mesh axis (capability absent from the reference, SURVEY §2.4).

Dense-dispatch formulation (einsum with one-hot dispatch/combine masks):
no gathers/scatters with dynamic shapes, so everything tiles onto the MXU
and the only cross-device traffic is two all_to_alls on [experts, capacity,
model] buffers riding ICI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def top1_routing(router_logits, capacity: int):
    """router_logits: [N, E]. Returns (dispatch [N,E,C], combine [N,E,C],
    aux_loss scalar)."""
    n, e = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [N]
    expert_mask = jax.nn.one_hot(expert_idx, e, dtype=probs.dtype)  # [N,E]
    # load-balancing auxiliary loss (Switch Transformer eq. 4)
    density = expert_mask.mean(0)
    density_proxy = probs.mean(0)
    aux_loss = (density * density_proxy).sum() * e
    # position of each token within its expert's capacity buffer
    position = (jnp.cumsum(expert_mask, axis=0) - 1.0) * expert_mask  # [N,E]
    keep = (position < capacity).astype(probs.dtype) * expert_mask
    pos_onehot = jax.nn.one_hot(position.sum(-1).astype(jnp.int32), capacity,
                                dtype=probs.dtype)  # [N,C]
    dispatch = keep[:, :, None] * pos_onehot[:, None, :]  # [N,E,C]
    gate = (probs * expert_mask).sum(-1)  # [N]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, aux_loss


def _moe_local(x, router_w, w_in, w_out, *, axis_name: str,
               capacity_factor: float):
    """Inside shard_map over ep. x: [N_local, D] local tokens; router_w:
    [D, E_total]; w_in/w_out: this shard's experts [E_local, D, F] /
    [E_local, F, D]."""
    ep = jax.lax.axis_size(axis_name)
    n_local, d = x.shape
    e_local = w_in.shape[0]
    e_total = e_local * ep
    capacity = max(1, int(capacity_factor * n_local / e_total))

    logits = x @ router_w  # [N_local, E_total]
    dispatch, combine, aux = top1_routing(logits, capacity)

    # [N,E,C] x [N,D] -> [E_total, C, D] -> group by owner shard
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)
    expert_in = expert_in.reshape(ep, e_local, capacity, d)
    # all_to_all: shard i sends block j to shard j; receives [ep, e_local,C,D]
    expert_in = jax.lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
    # -> [ep(sources), e_local, C, D]; fold sources into capacity
    expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
        e_local, ep * capacity, d)

    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, w_in))
    y = jnp.einsum("ecf,efd->ecd", h, w_out)  # [e_local, ep*C, D]

    y = y.reshape(e_local, ep, capacity, d).transpose(1, 0, 2, 3)
    y = jax.lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)  # back: [ep, e_local, C, D]
    y = y.reshape(e_total, capacity, d)
    out = jnp.einsum("nec,ecd->nd", combine, y)
    return out.astype(x.dtype), aux[None]


def moe_apply(x, router_w, w_in, w_out, *, mesh: Mesh,
              capacity_factor: float = 1.25, axis_name: str = "ep",
              token_axis: str = "dp"):
    """Driver-level entry. x: [N, D] tokens (sharded over dp); w_in/w_out:
    [E, D, F] / [E, F, D] sharded over ep on the expert axis."""
    fn = jax.shard_map(
        functools.partial(_moe_local, axis_name=axis_name,
                          capacity_factor=capacity_factor),
        mesh=mesh,
        in_specs=(P(token_axis, None), P(), P(axis_name), P(axis_name)),
        out_specs=(P(token_axis, None), P(token_axis)),
        check_vma=False,
    )
    out, aux = fn(x, router_w, w_in, w_out)
    return out, jnp.mean(aux)
