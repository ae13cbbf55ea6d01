"""Device-to-host fetches per query completed: the ``device`` counter
``host_syncs`` (every call of ``repro.core.prims.to_host``) over the
window.  None from a program whose spans carry no ``trace_id``, which
has no such counter."""


def read(run):
    if not run.latencies_s or not run.spans or not hasattr(run.spans[0], "trace_id"):
        return None
    return run.counters.get("device", {}).get("host_syncs", 0) / len(run.latencies_s)
