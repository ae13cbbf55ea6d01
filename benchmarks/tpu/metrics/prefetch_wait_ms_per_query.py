"""Time the streamed path's dispatching thread waits on the chunk
prefetch thread, per query completed, in ms: the
``pipeline.prefetch_wait`` spans of ``repro.obs``, summed over the
window.  None from a program whose spans carry no ``trace_id``, which
records no such span at this tracing level."""


def read(run):
    if not run.latencies_s or not run.spans or not hasattr(run.spans[0], "trace_id"):
        return None
    ns = sum(s.dur_ns for s in run.spans if s.name == "pipeline.prefetch_wait")
    return 1e-6 * ns / len(run.latencies_s)
