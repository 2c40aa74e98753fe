"""Device busy time of the traced calls per engine round, in ms; the mean
over the chips, each of which runs every round for its own drives."""


def read(run):
    if run.trace is None:
        return None
    rounds = run.trace.calls * run.rounds_per_call
    return run.trace.busy_mean_s / rounds * 1e3
