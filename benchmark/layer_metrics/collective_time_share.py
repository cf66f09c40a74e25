"""Collectives: device time of collective operations over device busy
time, in the traced steps, averaged over the chips — ``all-gather``,
``all-reduce``, ``reduce-scatter``, ``collective-permute`` and
``all-to-all`` operations by the name the ``XLA Ops`` line gives them,
their ``-start`` / ``-done`` halves included, and the TPU compiler's
``async-collective-start`` / ``-done`` (an asynchronous collective it
does not name further). The reducer gives every
instant to the latest-started operation that covers it, so a collective
that runs under compute counts only where nothing started after it: the
time not hidden under compute."""

import re

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|async-collective)")


def read(host, trace):
    if not trace or not trace["busy_s"]:
        return None
    own = sum(s for name, s in trace["op_self_s"].items()
              if COLLECTIVE.match(name))
    return 100.0 * own / trace["busy_s"]
