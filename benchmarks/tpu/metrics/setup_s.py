"""Process start to the first timed request: imports, data, the load
into the deployment and the warm-up (compiles or cache loads)."""


def read(run):
    return run.setup_s
