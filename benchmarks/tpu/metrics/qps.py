"""Queries completed per second over the whole measured window."""


def read(run):
    if run.window_s <= 0 or not run.latencies_s:
        return None
    return len(run.latencies_s) / run.window_s
