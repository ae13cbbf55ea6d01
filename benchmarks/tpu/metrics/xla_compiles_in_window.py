"""XLA programs compiled inside the measured window (loads from the
persistent cache do not count): should read 0."""


def read(run):
    return run.xla_compiles
