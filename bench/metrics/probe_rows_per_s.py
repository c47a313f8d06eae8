"""Probe rows joined inside the window, per second: the pending rows of
every bucket the window's rounds serviced (``queue_size`` at selection,
from the dispatch loop's round tap)."""


def read(run):
    return sum(rows for _, served in run.rounds for _, rows in served) / run.seconds
