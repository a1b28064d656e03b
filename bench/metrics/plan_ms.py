"""Mean host milliseconds per window round in ``round_batch`` (cohort draw,
reshuffle, padding, token fill), timed by the harness.  ``as_device_batch``
is left out: with rounds dispatched ahead, the host also waits there for the
runtime's queue of rounds, which is the device's time and not the data plane's."""


def read(run):
    return 1e3 * sum(r.plan_s for r in run.rounds) / len(run.rounds)
