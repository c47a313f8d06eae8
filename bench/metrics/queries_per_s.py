"""Queries completed inside the window, per second of the window."""


def read(run):
    return run.completed / run.seconds
