"""Share of the streamed chunks that folded into a dense device
accumulator: 100 x ``core.pipeline`` ``dense_agg_chunks`` /
``chunks_streamed`` over the window, 0 where no chunk streamed.  None
from a program whose ``core.pipeline`` counters have no
``dense_agg_chunks`` (the window's deltas leave out a counter that did
not move, so the program's own registry is asked)."""


def read(run):
    from repro import obs

    if "dense_agg_chunks" not in obs.metrics.snapshot().get("core.pipeline", {}):
        return None
    group = run.counters.get("core.pipeline", {})
    streamed = group.get("chunks_streamed", 0)
    return 100.0 * group.get("dense_agg_chunks", 0) / streamed if streamed else 0.0
