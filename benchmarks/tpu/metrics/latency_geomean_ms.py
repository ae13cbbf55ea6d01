"""Geometric mean of the latency of every query completed in the window,
in ms: each query type weighs the same, as in TPC-H's Power metric."""
import math


def read(run):
    if not run.latencies_s:
        return None
    return 1e3 * math.exp(sum(math.log(t) for t in run.latencies_s) / len(run.latencies_s))
