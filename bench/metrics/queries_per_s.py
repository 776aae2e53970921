"""Queries completed in the window over the window's length (its open to
the last result read back)."""


def read(run):
    return len(run.completed) / run.window_s
