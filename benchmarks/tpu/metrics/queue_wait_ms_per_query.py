"""Admission queue wait per query completed, in ms: the running total of
``serve.STATS``'s queue phase (submit to batch start, the straggler
window included) over the window.  None from a program without the
running total."""


def read(run):
    queue = run.counters.get("serve", {}).get("phases", {}).get("queue", {})
    if not run.latencies_s or "n" not in queue:
        return None
    return queue.get("total_ms", 0.0) / len(run.latencies_s)
