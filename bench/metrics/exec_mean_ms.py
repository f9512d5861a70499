"""Mean time of one plan-set call with its copies, as the server times it
around each batch, differenced across the window."""


def read(run):
    c = run.window.counters
    if not c.get("exec_n"):
        return None
    return c["exec_s"] / c["exec_n"] * 1e3
