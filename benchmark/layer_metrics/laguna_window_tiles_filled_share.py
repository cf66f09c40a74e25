"""Kernels: how much of what the attention kernels multiply on the
window layers lies inside causal AND window — ``window_scores_inside``
over ``window_scores_visited`` on the traced call's ``train.dispatch``
span (a step's score entries inside the mask, the forward's counted
twice for its rematerialised copy and the backward's once, over the
entries of the tiles the kernels' loops walk for them: the program
reckons both from the bounds and tiles its kernels use), in percent. A
window of one tile side fills a half at best: every 512 keys seen lie
across two tiles. A program whose span carries no such fact gives
None."""

from benchmark.this_cell import traced_call_attrs


def read(host, trace):
    facts = traced_call_attrs("train.dispatch") or {}
    if not facts.get("window_scores_visited"):
        return None
    return 100.0 * facts["window_scores_inside"] \
        / facts["window_scores_visited"]
