"""idle_share: 1 - device busy time / the traced window, from the
profiler trace of a traced run (chipbench/tracing.py)."""


def read(run):
    if run.trace is None:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
