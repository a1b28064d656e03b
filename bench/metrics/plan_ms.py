"""Mean host milliseconds per window round in ``round_batch`` and
``as_device_batch`` (batch assembly and transfer), timed by the harness."""


def read(run):
    return 1e3 * sum(r.plan_s for r in run.rounds) / len(run.rounds)
