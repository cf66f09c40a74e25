"""Compile caches: how much of the run's first ``train()`` call has a
name — the share of ``call_log()[0]``'s ``train.call`` inside the union
of the ``compile.*`` spans, ``jax.compile``, ``train.sync`` and
``train.snapshot`` (``benchmark/start_log.py``); percent."""

from benchmark import start_log


def read(host, trace):
    entry = start_log.first_call_entry(host)
    if entry is None:
        return None
    return start_log.named_share(entry, "train.call",
                                 start_log.FIRST_CALL_NAMED)
