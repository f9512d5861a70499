"""Rows whose answer came back in the window, over the window."""


def read(run):
    w = run.window
    return w.rows / w.seconds if w.rows else None
