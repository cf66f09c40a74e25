"""Operator: what the selection saves ON THIS CHIP — the score tiles of
the forward kernel's walk that hold a selected pair and are run, over
the tiles at or below the diagonal (``index_tiles_visited`` over
``index_tiles_causal`` on a call's ``train.sync`` span, summed over
batch rows, layers and the call's steps; the plane is one a batch row,
so every head walks the same), median over the window's calls, in
percent. With 2048 keys a query scattered over up to 16 384 nearly
every 256 x 512 tile holds one: about 100 %, and what a later
optimisation starts from. A program whose spans carry no such counters
gives None."""

from benchmark.layer_metrics.index_selected_share import ratio


def read(host, trace):
    return ratio(host, "index_tiles_visited", "index_tiles_causal")
