"""A training state crosses the object plane in pieces the arena can hold.

The epoch-boundary snapshot (`Trainer.train`) and the elastic restore
move the whole training state between a worker and the driver. One
object is fine while it fits the node's shared arena (GPT-2-small +
AdamW: 1.39 GiB of 2 GiB); GPT-2-large's 9.3 GB is not. So the state
always moves as a sequence of PIECES, each a run of whole leaves in tree
order (`jax.tree.flatten`), bounded by a budget derived from what the
store holds before it spills (`usable_bytes`: an observable, not an
option). A state that fits the store is cut by the same budget as one
that does not (GPT-2-small: four pieces), so that the link, the
worker's put and the driver's copy run beside each other; one smaller
than a single budget is one piece: the degenerate case of the same code.
The worker's side of both directions is here; the driver's is
`Trainer._pull_state` / `_push_state`.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import numpy as np

# A piece is at most this share of what the store holds: the worker can
# then put the next one (or two) while the driver still copies the last.
PIECE_SHARE = 4


def usable_bytes(core_worker) -> int:
    """What the node's object store holds before the raylet starts to
    spill: its capacity (read from the store itself) times the spilling
    threshold. A store that keeps no count (the file-per-object
    fallback) is bounded by the configured size."""
    stats = getattr(core_worker.store, "stats", None)
    capacity = (stats()["capacity"] if stats is not None
                else core_worker.config.object_store_memory)
    return int(capacity * core_worker.config.object_spilling_threshold)


def leaf_bytes(x) -> int:
    return int(getattr(x, "nbytes", 0))


def plan(sizes: list[int], usable: int) -> list[tuple[int, int]]:
    """Leaf index ranges [first, stop), in order, covering `sizes`:
    runs of whole leaves of at most `usable // PIECE_SHARE` bytes,
    whether or not all of it fits `usable`. A leaf larger than that is
    a piece of its own; one larger than `usable` cannot cross at all."""
    largest = max(sizes, default=0)
    if largest > usable:
        raise ValueError(
            f"a leaf of the training state is {largest} bytes, more than "
            f"the object store's arena holds before it spills ({usable} "
            "bytes): it cannot cross the object plane whole; start the "
            "runtime with a larger object_store_memory")
    budget = usable // PIECE_SHARE
    pieces, first, held = [], 0, 0
    for i, size in enumerate(sizes):
        if i > first and held + size > budget:
            pieces.append((first, i))
            first, held = i, 0
        held += size
    pieces.append((first, len(sizes)))
    return pieces


class Cut(NamedTuple):
    """A state dict cut for a store that holds `usable` bytes: its
    leaves in tree order (where they are: nothing is moved), their
    sizes, and the leaf range of every piece."""

    leaves: list
    treedef: Any
    sizes: list[int]
    ranges: list[tuple[int, int]]

    def part(self, index: int) -> list:
        """The leaves of piece `index`; none past the last piece."""
        if index >= len(self.ranges):
            return []
        first, stop = self.ranges[index]
        return self.leaves[first:stop]

    def reply(self, index: int, leaves: list) -> dict:
        """What crosses for piece `index`: `leaves` (the piece's, on
        the host), `first` (the tree-order index of the first one) and,
        with piece 0, the plan: `treedef` and every piece's leaf range
        and `bytes`."""
        out = {"first": self.ranges[index][0], "leaves": leaves}
        if index == 0:
            out["treedef"] = self.treedef
            out["ranges"] = self.ranges
            out["bytes"] = [sum(self.sizes[a:b]) for a, b in self.ranges]
        return out


def cut(tree, usable: int) -> Cut:
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [leaf_bytes(x) for x in leaves]
    return Cut(leaves, treedef, sizes, plan(sizes, usable))


def piece(tree, index: int, usable: int) -> dict:
    """Piece `index` of `tree`, a state dict whose leaves are on the
    host already (`Cut.reply`)."""
    whole = cut(tree, usable)
    return whole.reply(index, whole.part(index))


class Assembler:
    """The receiving side of a pushed state for an operator that only
    has `load_state_dict`: keeps each piece's leaves (copied out of the
    arena, whose views die with the call's arguments) until the tree is
    whole."""

    def __init__(self):
        self._treedef, self._leaves = None, []

    def add(self, first: int, leaves: list, treedef=None):
        """-> the whole state once its last leaf is in, else None."""
        if first == 0:
            self._treedef, self._leaves = treedef, []
        if self._treedef is None or first != len(self._leaves):
            raise ValueError(
                f"state piece starting at leaf {first} arrived after "
                f"{len(self._leaves)} leaves: pieces come in order, "
                "from leaf 0")
        self._leaves.extend(
            np.array(x) if isinstance(x, np.ndarray) else x for x in leaves)
        if len(self._leaves) < self._treedef.num_leaves:
            return None
        state = jax.tree.unflatten(self._treedef, self._leaves)
        self._treedef, self._leaves = None, []
        return state
