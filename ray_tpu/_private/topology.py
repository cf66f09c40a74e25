"""Topology plane: physical pod shape in the resource model (ROADMAP
item 2 / ISSUE 14 tentpole).

Every raylet derives a `TopologyCoord` — (slice id, torus coords, host
id) — and registers it into the GCS node table; placement, spillback
ordering, and locality tie-breaking all consume the same graded
`distance()` metric:

    same-process/host  <  same-slice-by-ICI-hops  <  cross-slice (DCN)

Coords come from (in priority order):
  1. an explicit coord dict (cluster_utils.add_node(topology=...), the
     scale-sim's spoofed raylets, raylet --topology);
  2. the `RAY_TPU_TOPOLOGY` env var (JSON: {"slice_id","coords","dims"})
     — how CPU clusters and sim processes synthesize a torus without
     TPU hardware;
  3. the node's TpuSliceDescriptor (util/accelerators.py): host_index
     laid onto a host grid factored from the slice's chip topology;
  4. none — the node has no coord; ICI_RING falls back to PACK (counted
     by `gcs.placement_topology_fallbacks_total`).

The placement *cost model* is a first-class pluggable object
(`PlacementCostModel.score(bundles, candidates) -> cost`, lower wins):
the default scores candidate ring orderings by torus circumference; a
registered alternative (by name, or a "module:attr" spec the GCS
imports — the Placeto direction, scored from the PR 6/13 metrics
history via `bind_context`) can replace the heuristic per request and
be A/B'd in the scale-sim harness.
"""

from __future__ import annotations

import dataclasses
import json
import os

# distance grading constants: any same-slice distance (1 + hops) must
# stay strictly below a cross-slice one — torus dims are physically
# bounded (largest public slice topologies are O(100) hops across), so
# a 4-digit base keeps the bands disjoint without float games.
D_SAME_PROCESS = 0.0
D_SAME_HOST = 0.5
D_CROSS_SLICE = 1.0e4

ENV_VAR = "RAY_TPU_TOPOLOGY"


@dataclasses.dataclass(frozen=True)
class TopologyCoord:
    """One node's position in the pod's physical shape.

    slice_id: opaque ICI-domain id (equal slice_id <=> ICI-reachable)
    coords:   this host's torus coordinates within the slice
    dims:     torus dimensions (wraparound lengths per axis)
    host_id:  node identity (node-id hex) — equal host_id <=> the same
              raylet/host, the shm domain
    """

    slice_id: str
    coords: tuple[int, ...]
    dims: tuple[int, ...]
    host_id: str = ""

    def to_dict(self) -> dict:
        return {"slice_id": self.slice_id, "coords": list(self.coords),
                "dims": list(self.dims), "host_id": self.host_id}

    @classmethod
    def from_dict(cls, d: dict | None) -> "TopologyCoord | None":
        if not d or not d.get("slice_id"):
            return None
        return cls(slice_id=str(d["slice_id"]),
                   coords=tuple(int(c) for c in d.get("coords") or ()),
                   dims=tuple(int(x) for x in d.get("dims") or ()),
                   host_id=str(d.get("host_id") or ""))


def _host_grid(num_hosts: int, topology: tuple[int, ...]) -> tuple[int, ...]:
    """Factor `num_hosts` into a grid roughly proportional to the chip
    topology (hosts tile the slice along its major axes). Greedy: peel
    the largest factor of num_hosts that divides each topology axis."""
    if num_hosts <= 1:
        return (1,)
    remaining = num_hosts
    grid = []
    for axis in topology:
        f = 1
        # largest divisor of `remaining` that fits the axis
        for cand in range(min(axis, remaining), 0, -1):
            if remaining % cand == 0:
                f = cand
                break
        grid.append(f)
        remaining //= f
        if remaining == 1:
            break
    if remaining > 1:
        grid.append(remaining)
    return tuple(grid)


def _coords_of_index(index: int, dims: tuple[int, ...]) -> tuple[int, ...]:
    """Row-major coords of a flat index in a grid."""
    out = []
    for d in reversed(dims):
        out.append(index % d)
        index //= d
    return tuple(reversed(out))


def derive_coord(*, node_id_hex: str, tpu_slice: dict | None = None,
                 labels: dict | None = None, explicit: dict | None = None,
                 env: dict | None = None) -> TopologyCoord | None:
    """Derive this node's TopologyCoord deterministically (no randomness:
    a restarted raylet must land on the same coord). Returns None when
    the node has no topology identity at all — placement then falls
    back and counts it, rather than inventing fake adjacency."""
    env = os.environ if env is None else env
    for source in (explicit, _parse_env(env), (labels or {}).get("topology")):
        coord = TopologyCoord.from_dict(source) if isinstance(source, dict) \
            else None
        if coord is not None:
            if not coord.host_id:
                coord = dataclasses.replace(coord, host_id=node_id_hex)
            return coord
    if tpu_slice and tpu_slice.get("slice_id"):
        topo = tuple(int(t) for t in tpu_slice.get("topology") or (1,))
        num_hosts = int(tpu_slice.get("num_hosts") or 1)
        grid = _host_grid(num_hosts, topo)
        return TopologyCoord(
            slice_id=str(tpu_slice["slice_id"]),
            coords=_coords_of_index(int(tpu_slice.get("host_index") or 0),
                                    grid),
            dims=grid, host_id=node_id_hex)
    return None


def _parse_env(env) -> dict | None:
    raw = env.get(ENV_VAR) if env else None
    if not raw:
        return None
    try:
        d = json.loads(raw)
        return d if isinstance(d, dict) else None
    except (ValueError, TypeError):
        return None


# ---------------------------------------------------------------------------
# distance
# ---------------------------------------------------------------------------


def torus_hops(a: tuple[int, ...], b: tuple[int, ...],
               dims: tuple[int, ...]) -> int:
    """ICI hop count between two coords on a wraparound torus (per-axis
    minimum of forward/backward walks, summed — the physical link
    count). Missing axes/dims degrade to non-wrapping manhattan."""
    hops = 0
    for i in range(max(len(a), len(b))):
        ai = a[i] if i < len(a) else 0
        bi = b[i] if i < len(b) else 0
        delta = abs(ai - bi)
        if i < len(dims) and dims[i] > 0:
            delta = min(delta, dims[i] - delta)
        hops += delta
    return hops


def distance(a: TopologyCoord | None, b: TopologyCoord | None) -> float:
    """Graded wire distance between two nodes: same host < same slice
    (1 + ICI hops) < cross-slice/DCN. Unknown coords read as cross-slice
    — an unlocatable node is never preferred over a located one."""
    if a is None or b is None:
        return D_CROSS_SLICE
    if a.host_id and a.host_id == b.host_id:
        return D_SAME_PROCESS if a.coords == b.coords else D_SAME_HOST
    if a.slice_id != b.slice_id:
        return D_CROSS_SLICE
    return 1.0 + torus_hops(a.coords, b.coords, a.dims or b.dims)


def nearest_first(origin: TopologyCoord | None, items: list,
                  key) -> list:
    """Stable-sort `items` by graded distance from `origin` (`key`
    extracts each item's TopologyCoord-or-None). Unknown origin leaves
    the order untouched — no coords, no opinion; equal distances keep
    their input order so callers' prior ranking survives as the
    tie-break within a band."""
    if origin is None:
        return list(items)
    return sorted(items, key=lambda it: distance(origin, key(it)))


# ---------------------------------------------------------------------------
# ring ordering (the ICI_RING strategy's geometry)
# ---------------------------------------------------------------------------


def snake_key(coord: TopologyCoord) -> tuple:
    """Boustrophedon (snake) ordering key over the torus grid:
    consecutive positions in snake order are ICI neighbors, so any
    contiguous window of located nodes forms a low-circumference ring.
    Odd-indexed rows reverse, per axis, like a pmap device raster."""
    c, dims = coord.coords, coord.dims
    key = []
    flip = False
    for i, v in enumerate(c):
        d = dims[i] if i < len(dims) else 0
        key.append((d - 1 - v) if (flip and d) else v)
        # parity of everything placed so far decides the next axis's
        # direction; approximate with this axis's parity
        flip = bool(v % 2) ^ flip
    return tuple(key)


def ring_circumference(coords: list[TopologyCoord | None]) -> float:
    """Total wire distance around the bundle ring, including the wrap
    hop rank N-1 -> rank 0 (what the collective ring transports pay per
    pass). Same-host consecutive ranks count 0."""
    n = len(coords)
    if n <= 1:
        return 0.0
    total = 0.0
    for i in range(n):
        a, b = coords[i], coords[(i + 1) % n]
        if a is not None and b is not None and a.host_id \
                and a.host_id == b.host_id:
            continue  # same host: the hop is shm/loopback, not a wire
        if a is None or b is None or a.slice_id != b.slice_id:
            total += D_CROSS_SLICE
        else:
            total += float(torus_hops(a.coords, b.coords,
                                      a.dims or b.dims))
    return total


# ---------------------------------------------------------------------------
# device-count -> (data, fsdp) mesh shapes (SNIPPETS [2]; public home:
# parallel/mesh.py re-exports — this module stays jax-free so the GCS
# placement scorer can share the table)
# ---------------------------------------------------------------------------

# Rationale (SNIPPETS [2]): fsdp=4 saturates the fastest ICI links (4
# chips per tray share them), data scales linearly with pod size; tiny
# slices stay pure-DP.
MESH_SHAPES: dict[int, tuple[int, int]] = {
    1: (1, 1),
    2: (2, 1),
    4: (4, 1),
    8: (8, 1),       # v5p-8: pure DP
    16: (8, 2),
    32: (8, 4),
    64: (16, 4),
    128: (32, 4),
    256: (64, 4),
    512: (128, 4),
    768: (192, 4),
}


def mesh_shape_for(num_devices: int) -> tuple[int, int]:
    """(data, fsdp) mesh shape for `num_devices` devices. Table sizes
    resolve directly; other counts synthesize per the same rationale —
    fsdp is the largest power-of-two divisor up to 4 (the ICI-saturating
    tray width), data fills the rest. Always satisfies
    data * fsdp == num_devices."""
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    shape = MESH_SHAPES.get(num_devices)
    if shape is not None:
        return shape
    return host_mesh_shape(num_devices)


def host_mesh_shape(num_devices: int) -> tuple[int, int]:
    """(data, fsdp) for the chips ONE process holds — the chips of one
    host, all on ICI: fsdp is the largest power-of-two divisor up to 4
    (the tray width), data the rest. The table's pure-DP rows for tiny
    slices are about gangs of hosts; on one host's chips the state is
    sharded (a v5e host's four chips: (1, 4))."""
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    fsdp = 4 if num_devices % 4 == 0 else (2 if num_devices % 2 == 0 else 1)
    return (num_devices // fsdp, fsdp)


# ---------------------------------------------------------------------------
# pluggable placement cost model (Placeto direction, PAPERS.md)
# ---------------------------------------------------------------------------


class PlacementCostModel:
    """Scores one candidate bundle->node assignment; the GCS picks the
    candidate with the LOWEST score. `candidates` is the assignment
    as a list of TopologyCoord-or-None, one per bundle rank, in rank
    order. Implementations may define `bind_context(ctx)` to receive
    {"metrics_history": ...} before a scoring round."""

    name = "base"

    def bind_context(self, ctx: dict) -> None:  # pragma: no cover - hook
        pass

    def score(self, bundles: list[dict],
              candidates: list) -> float:
        raise NotImplementedError


class RingDistanceCostModel(PlacementCostModel):
    """Default heuristic: the ring circumference of the assignment —
    minimal total ICI wire around consecutive ranks (incl. the wrap)."""

    name = "ring"

    def score(self, bundles, candidates) -> float:
        return ring_circumference(list(candidates))


class MetricsTrendCostModel(PlacementCostModel):
    """Metrics-history-scored model (the learned-policy socket, per
    Placeto): ring circumference plus a penalty per node whose raylet
    reported rising spillback counts over the bound history window —
    hot nodes repel new gangs. The GCS binds its live
    `metrics_history` rings before each scoring round; scored offline
    it degrades to the plain ring heuristic."""

    name = "metrics"

    def __init__(self, history: int = 30, penalty: float = 2.0):
        self._history = history
        self._penalty = penalty
        self._hot: set[str] = set()

    def bind_context(self, ctx: dict) -> None:
        hot: set[str] = set()
        for source, rings in (ctx.get("metrics_history") or {}).items():
            ring = rings.get("raylet.spillbacks_total")
            if not ring:
                continue
            window = list(ring)[-self._history:]
            if len(window) >= 2 and window[-1][1] > window[0][1]:
                # source is "<node8>/raylet": key by the node-id prefix
                hot.add(source.split("/", 1)[0])
        # coords registered with an EXPLICIT host_id never equal the
        # node-id hex the metric sources carry; the GCS passes its
        # node8 -> coord-host_id map so those nodes stay penalizable
        for n8, host_id in (ctx.get("node_hosts") or {}).items():
            if n8 in hot and host_id:
                hot.add(host_id)
        self._hot = hot

    def score(self, bundles, candidates) -> float:
        cost = ring_circumference(list(candidates))
        for c in candidates:
            if c is not None and (c.host_id in self._hot
                                  or c.host_id[:8] in self._hot):
                cost += self._penalty
        return cost


_COST_MODELS: dict[str, PlacementCostModel] = {}


def register_cost_model(model: PlacementCostModel,
                        name: str | None = None) -> None:
    """Register a model instance under `name` (defaults to model.name)
    in THIS process. The GCS resolves names through this registry, so
    in-process registration only reaches a GCS running in the same
    process (unit tests); cross-process, pass a "module:attr" spec
    instead — the GCS imports it."""
    _COST_MODELS[name or model.name] = model


def resolve_cost_model(spec: str | None) -> PlacementCostModel:
    """Resolve a cost-model spec: None/"" /"ring" -> the default ring
    heuristic; a registered name; or "module:attr" imported dynamically
    (attr may be an instance or a zero-arg class). Raises ValueError on
    an unknown spec — placement_group() surfaces it typed at creation,
    not as a silently-wrong placement."""
    if not spec or spec == "ring":
        return _DEFAULT_MODEL
    if spec in _COST_MODELS:
        return _COST_MODELS[spec]
    if ":" in spec:
        mod_name, _, attr = spec.partition(":")
        import importlib

        try:
            obj = getattr(importlib.import_module(mod_name), attr)
        except (ImportError, AttributeError) as e:
            raise ValueError(
                f"placement cost model {spec!r} failed to import: {e}")
        model = obj() if isinstance(obj, type) else obj
        if not hasattr(model, "score"):
            raise ValueError(
                f"placement cost model {spec!r} has no score()")
        _COST_MODELS[spec] = model
        return model
    raise ValueError(
        f"unknown placement cost model {spec!r}; registered: "
        f"{sorted(_COST_MODELS) + ['ring']} or a 'module:attr' spec")


_DEFAULT_MODEL = RingDistanceCostModel()
register_cost_model(_DEFAULT_MODEL)
register_cost_model(MetricsTrendCostModel())


# ---------------------------------------------------------------------------
# placement-derived collective transport
# ---------------------------------------------------------------------------


def transport_plan(pg_record: dict | None) -> dict | None:
    """Derive the collective transport tier a gang formed from this
    placement record should use — the placement GUARANTEED the
    geometry, so the group skips the unanimous probe round (shm
    rendezvous / device vote) entirely. Returns
    {"transport", "ranks": [{"node","slice_id","coords"}...],
     "ring_circumference"} or None when the record carries no topology
    plan (ad-hoc groups keep probing).

    Tier choice from the gang's geometry: every rank on one node ->
    shm; every rank in one ICI slice with TPU chips reserved AND a live
    TPU backend in the deriving process -> device; >2 ranks ->
    pipelined ring; else hub (a 2-rank ring degenerates). The backend
    check keeps a CPU box from pinning a tier the gang cannot build —
    that would demote at runtime (host_backend._demote_derived) and
    re-open the probe rounds the derivation exists to skip. A derived
    tier stays a SOFT pin: ranks whose runtime still cannot build it
    demote to auto routing in unison instead of raising like a
    user-forced transport."""
    if not pg_record or pg_record.get("state") != "CREATED":
        return None
    plan = pg_record.get("topology_plan")
    bundles = pg_record.get("bundles") or []
    if not plan or not bundles:
        return None
    coords = [TopologyCoord.from_dict(b.get("topology")) for b in bundles]
    nodes = [b.get("node_id") for b in bundles]
    ranks = [{"node": (n.hex()[:8] if isinstance(n, bytes) else str(n)),
              "slice_id": c.slice_id if c else None,
              "coords": list(c.coords) if c else None}
             for n, c in zip(nodes, coords)]
    world = len(bundles)
    if world > 1 and len(set(nodes)) == 1:
        transport = "shm"
    elif (world > 1 and all(c is not None for c in coords)
          and len({c.slice_id for c in coords}) == 1
          and all(_bundle_tpu(b) > 0 for b in bundles)
          and _tpu_backend_live()):
        # the same ICI geometry that admits the device tier admits the
        # fused-kernel refinement; PALLAS stays opt-in
        # (RAY_TPU_PALLAS_DERIVE=1) because a derived pin is still a
        # pin — ops under pallas_max_bytes run the kernel tier, larger
        # ones fall through to device — and the default AUTO route
        # already prefers pallas for small device arrays
        transport = ("pallas" if _pallas_derive_enabled() else "device")
    elif world > 2:
        transport = "ring"
    else:
        transport = "hub"
    return {"transport": transport, "ranks": ranks,
            "ring_circumference": ring_circumference(coords),
            "cost_model": pg_record.get("cost_model") or "ring",
            "strategy": pg_record.get("strategy")}


def _pallas_derive_enabled() -> bool:
    """Whether ICI_RING placement records derive the PALLAS tier
    instead of DEVICE (both soft pins; pallas additionally needs the
    kernel machinery importable in the deriving process)."""
    import os

    if os.environ.get("RAY_TPU_PALLAS_DERIVE", "0") in ("0", "false", ""):
        return False
    try:
        from ray_tpu.collective.backends.pallas_backend import (
            pallas_supported)

        return pallas_supported()
    except Exception:
        return False


def _tpu_backend_live() -> bool:
    """Whether THIS process runs a live TPU jax backend. Lazy import:
    the module stays importable in jax-free processes (GCS scorer)."""
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _bundle_tpu(bundle: dict) -> float:
    res = bundle.get("resources") or {}
    try:
        from ray_tpu._private.common import ResourceSet

        return ResourceSet.from_raw(res).get("TPU")
    except Exception:
        return float(res.get("TPU", 0) or 0)
