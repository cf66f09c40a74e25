"""Device mesh construction for 5-axis parallelism.

The TPU-native resource model the reference lacks (SURVEY §2.4: TP/PP/SP/EP
absent upstream): one jax Mesh with named axes

    dp — data parallel (gradient allreduce; DCN-friendly outer axis)
    pp — pipeline stages (ppermute microbatch schedule)
    sp — sequence/context parallel (ring attention)
    tp — tensor parallel (heads/mlp sharding; highest-bandwidth ICI axis)
    ep — expert parallel (MoE all_to_all)

Axis order puts dp outermost and tp innermost so tp collectives ride the
fastest ICI links on real slices (the "How to Scale Your Model" recipe:
mesh axes ordered by communication intensity).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "pp", "sp", "tp", "ep")

# Device-count -> (data, fsdp) 2D mesh shapes (SNIPPETS [2]: the
# auto-sharder's predefined optimal shapes for TPU pod slices).
# ROADMAP item 3's FSDP ('data','fsdp') mode consumes this, and the
# ICI_RING placement strategy records it with each gang so rank
# ordering and the derived mesh agree on the same factorization. The
# implementation lives jax-free in _private/topology.py because the
# GCS placement scorer (a control-plane process that never imports
# jax) shares it; this is its public home.
from ray_tpu._private.topology import (  # noqa: E402  (re-export)
    MESH_SHAPES as _MESH_SHAPES,
    host_mesh_shape,
    mesh_shape_for,
)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    dp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.sp * self.tp * self.ep

    def axis_sizes(self) -> tuple[int, ...]:
        return (self.dp, self.pp, self.sp, self.tp, self.ep)

    @classmethod
    def auto(cls, n_devices: int, *, tp: int = 1, pp: int = 1, sp: int = 1,
             ep: int = 1) -> "MeshSpec":
        """Fill dp with whatever devices remain after the model axes."""
        model = tp * pp * sp * ep
        if n_devices % model:
            raise ValueError(
                f"{n_devices} devices not divisible by tp*pp*sp*ep={model}")
        return cls(dp=n_devices // model, pp=pp, sp=sp, tp=tp, ep=ep)

    def build(self, devices=None) -> Mesh:
        devices = list(devices) if devices is not None else jax.devices()
        if len(devices) < self.size:
            raise ValueError(
                f"mesh needs {self.size} devices, have {len(devices)}")
        devices = devices[: self.size]
        arr = np.array(devices).reshape(self.axis_sizes())
        return Mesh(arr, AXES)

    @classmethod
    def from_placement_group(cls, pg, *, tp: int | None = None, pp: int = 1,
                             sp: int = 1, ep: int = 1) -> "MeshSpec":
        """Derive the mesh from an actual TPU reservation, so shardings
        follow placement instead of convention (closing SURVEY §7 step 4:
        "STRICT_PACK = one ICI host" used to be a docstring).

        Each bundle is one slice host contributing its TPU chips. tp
        defaults to chips-per-host — tp is the innermost mesh axis, so
        tensor-parallel collectives ride the within-host ICI island; dp
        fills the remaining (cross-host) factor.
        """
        bundles = pg.bundle_specs if hasattr(pg, "bundle_specs") else pg
        chips = [int(b.get("TPU", 0)) for b in bundles]
        if not chips or any(c <= 0 for c in chips):
            raise ValueError(
                "placement group has bundles without TPU chips; "
                f"bundle resources: {bundles}")
        if len(set(chips)) != 1:
            raise ValueError(
                f"heterogeneous chips per bundle {chips}: a mesh needs "
                "equal chips per host")
        total = sum(chips)
        if tp is None:
            tp = chips[0]
        return cls.auto(total, tp=tp, pp=pp, sp=sp, ep=ep)


def fsdp_mesh(devices=None) -> Mesh:
    """The topology-derived ('data', 'fsdp') mesh of the Trainer's mesh
    mode (`mesh_mode="fsdp"`, or one worker that leases several chips).
    The chips of ONE process — one host, all on ICI — shard:
    `host_mesh_shape`, (1, 4) on a v5e host's four. Devices of several
    processes take `mesh_shape_for`'s table, the SAME one the ICI_RING
    placement record carries, so gang rank order and mesh layout agree.
    Params and optimizer state shard over 'fsdp', the batch over both
    axes."""
    devices = list(devices) if devices is not None else jax.devices()
    one_host = len({d.process_index for d in devices}) == 1
    shape = (host_mesh_shape if one_host else mesh_shape_for)(len(devices))
    n = shape[0] * shape[1]
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, ("data", "fsdp"))


def fsdp_param_specs(params, mesh: Mesh):
    """Per-leaf PartitionSpecs: each leaf is split over the 'fsdp' axis
    along the first dimension AFTER its leading one that divides evenly
    (a stacked block weight, 36 x 1280 x 5120, along 1280; GPT-2's
    embedding, 50257 x 1280, along 1280), along the leading one only
    when no later one does (and a one-dimensional leaf has no other);
    leaves with none (odd biases, scalars) stay replicated — the
    standard FSDP layout compromise. Leaves need a `shape` only
    (`jax.eval_shape` of an init will do).

    The leading dimension comes last because it is the one a model
    stacks its layers along and `lax.scan` walks: a stack split there
    puts whole layers on each device, every device needs every layer,
    and the compiler gathers the WHOLE stack for each layer's step of
    the scan (36 gathers of 36 layers a pass on GPT-2 large). Split
    along a later dimension, the scan's body gathers its own layer's
    slice and no more. Shapes cannot tell a stack from a plain matrix
    and need not: either dimension of a matrix serves ZeRO-3 equally.
    The EARLIEST later dimension, so that a shard stays a few long runs
    of the host copy a snapshot joins it into (`operator._to_host`)."""
    fsdp = mesh.shape["fsdp"]

    def spec(p):
        if fsdp > 1:
            shape = getattr(p, "shape", ())
            divides = [dim for dim, n in enumerate(shape)
                       if n >= fsdp and n % fsdp == 0]
            if divides:
                dim = next((d for d in divides if d > 0), 0)
                return P(*[None] * dim, "fsdp",
                         *[None] * (len(shape) - dim - 1))
        return P()

    return jax.tree.map(spec, params)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Inputs: batch over dp, sequence over sp."""
    return NamedSharding(mesh, P("dp", "sp"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_mesh_spec(*, tp: int = 1, pp: int = 1, sp: int = 1,
                    ep: int = 1) -> MeshSpec:
    return MeshSpec.auto(len(jax.devices()), tp=tp, pp=pp, sp=sp, ep=ep)
