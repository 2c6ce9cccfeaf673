"""qps: the queries a closed loop completed over the time from the
window's start to its last completion.  The loop starts rounds for the
window's length and every round it starts is waited for, so this is all
the work it did over all the time it took."""


def read(run):
    done = run.answered()
    if not done:
        return None
    return len(done) / (max(r.done for r in done) - run.window.start)
