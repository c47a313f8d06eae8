"""Mean wall time of one scheduling round (the benchmark's span around
``CrossMatchHost.step``), in milliseconds."""


def read(run):
    steps = [t1 - t0 for name, t0, t1 in run.spans if name == "step"]
    return 1e3 * sum(steps) / len(steps) if steps else None
