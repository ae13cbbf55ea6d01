"""Store chunks streamed through the chunk pipeline per query completed
(``core.pipeline`` counter ``chunks_streamed``)."""


def read(run):
    if not run.latencies_s:
        return None
    return run.counters.get("core.pipeline", {}).get("chunks_streamed", 0) / len(run.latencies_s)
