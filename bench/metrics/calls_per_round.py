"""Device calls the executor issued per scheduling round: the window's
change of ``DispatchLoop.device_dispatches`` over its change in rounds.
The shared plan issues one call per ``share_width`` chunk of a round's
queries, the fused path one a round."""


def read(run):
    rounds = run.counters.get("rounds")
    if not rounds:
        return None
    return run.counters["device_dispatches"] / rounds
