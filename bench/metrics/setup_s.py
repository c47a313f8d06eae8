"""Set-up: from the start of the process to the window's first query."""


def read(run):
    return run.setup_s
