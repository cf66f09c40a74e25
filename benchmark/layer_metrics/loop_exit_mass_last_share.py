"""Operator: where the loss's weight lies — the exit distribution's
mass on the LAST walk over the tokens scored (``exit_mass_T`` over
``loop_targets`` on a call's ``train.sync`` span), median over the
window's calls, in percent: 12.5 % over four walks with the gate at one
half, 100 % where no token exits early. A program whose spans carry no
such counters gives None."""

from benchmark.layer_metrics.loop_exit_entropy_share import median_of, walks


def read(host, trace):
    return median_of(host, lambda a: 100.0 * a[f"exit_mass_{walks(a)}"]
                     / a["loop_targets"])
