"""Device busy time (union of operation intervals in the profiler trace)
per query completed in the traced window, in ms."""


def read(run):
    t = run.trace
    if not t or not run.latencies_s or t["busy_s"] <= 0:
        return None
    return 1e3 * t["busy_s"] / len(run.latencies_s)
