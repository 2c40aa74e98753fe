"""Emulated requests retired in the window per wall second, all drives."""


def read(run):
    return run.retired / run.window_s
