"""Kernels: how much of one head's 2 L x 2 L score plane the forward
kernel's loops walk under the block-diffusion mask —
``attention_tiles_visited`` over ``attention_tiles_plane`` on the traced
call's ``train.dispatch`` span (the program reckons both from the bounds
its kernel uses), in percent. The mask holds 25 % of the plane + b / 4 L
(25.02 % here); the floor for a tiled walk is that plus the tiles the
two diagonals cross; causal over the same rows would walk 53 %. A
program whose span carries no such fact gives None."""

from benchmark.this_cell import traced_call_attrs


def read(host, trace):
    facts = traced_call_attrs("train.dispatch") or {}
    if not facts.get("attention_tiles_plane"):
        return None
    return 100.0 * facts["attention_tiles_visited"] \
        / facts["attention_tiles_plane"]
