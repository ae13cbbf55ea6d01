"""Host time building frames' device tensors per query completed, in
ms: the ``frame.upload`` spans of ``repro.obs`` (column stacking and the
host-to-device copy), summed over the window.  None from a program whose
spans carry no ``trace_id``, which has no such span."""


def read(run):
    if not run.latencies_s or not run.spans or not hasattr(run.spans[0], "trace_id"):
        return None
    ns = sum(s.dur_ns for s in run.spans if s.name == "frame.upload")
    return 1e-6 * ns / len(run.latencies_s)
