"""Mean wall time of one ``ServiceDaemon.submit`` (journal append, fsync,
decomposition into work units), in milliseconds."""


def read(run):
    sub = [t1 - t0 for name, t0, t1 in run.spans if name == "submit"]
    return 1e3 * sum(sub) / len(sub) if sub else None
