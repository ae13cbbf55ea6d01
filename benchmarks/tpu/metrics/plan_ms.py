"""Planning time per query completed, in ms: the ``serve.plan`` spans of
``repro.obs`` (parse, plan, optimize), summed over the window."""


def read(run):
    durs = [s.dur_ns for s in run.spans if s.name == "serve.plan"]
    if not durs or not run.latencies_s:
        return None
    return 1e-6 * sum(durs) / len(run.latencies_s)
