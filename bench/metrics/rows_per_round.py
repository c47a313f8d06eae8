"""Probe rows joined per scheduling round: the summed ``queue_size`` of
the buckets each round serviced, averaged over the window's rounds."""


def read(run):
    if not run.rounds:
        return None
    return sum(rows for _, served in run.rounds for _, rows in served) / len(run.rounds)
