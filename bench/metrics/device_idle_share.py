"""Share of the traced window in which no operation ran on the device,
in %; the largest over the chips."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_share_max * 100.0
