"""XLA compiles inside the window (JAX's backend-compile events)."""


def read(run):
    return run.counters["compiles"]
