"""Bytes handed from the host to the device per query completed, in MB
(1e6 bytes): the ``frame`` counter ``bytes_uploaded`` over the window.
None from a program whose spans carry no ``trace_id``, which has no
such counter."""


def read(run):
    if not run.latencies_s or not run.spans or not hasattr(run.spans[0], "trace_id"):
        return None
    nbytes = run.counters.get("frame", {}).get("bytes_uploaded", 0)
    return 1e-6 * nbytes / len(run.latencies_s)
