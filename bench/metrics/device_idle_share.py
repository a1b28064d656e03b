"""1 - device busy time / traced window, from the profiler trace of the
window; nothing without a trace."""


def read(run):
    if run.trace is None:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
