"""Host time in the store's scans per query completed, in ms: the
``store.scan`` spans of ``repro.obs`` (zone-map pruning, row masks,
chunk decode; on the prefetch thread for streamed chunks), summed over
the window.  None from a program whose spans carry no ``trace_id``,
which has no such span."""


def read(run):
    if not run.latencies_s or not run.spans or not hasattr(run.spans[0], "trace_id"):
        return None
    ns = sum(s.dur_ns for s in run.spans if s.name == "store.scan")
    return 1e-6 * ns / len(run.latencies_s)
