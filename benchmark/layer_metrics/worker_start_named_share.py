"""Control plane: how much of ``train.start`` has a name — the share of
the root's seconds inside the union of the stretch ``worker_spawn_s``
reads (root start to ``worker.boot`` end) and the leaf spans
``worker.chip_wait``, ``worker.actor_init``, ``train.setup.backend``,
``train.setup.user``, ``train.setup.init``, ``train.setup.place``
(``benchmark/start_log.py``); percent."""

from benchmark import start_log


def read(host, trace):
    entry = start_log.start_entry(host)
    spawn = entry and start_log.spawn_interval(entry)
    if not spawn:
        return None
    return start_log.named_share(entry, "train.start",
                                 start_log.START_LEAVES, [spawn])
