"""device_ms_per_query.qps: device busy time in the traced window over
the queries whose rounds ran in it."""


def read(run):
    if run.trace is None:
        return None
    done = run.answered()
    if not done:
        return None
    return 1e3 * run.trace["busy_s"] / len(done)
