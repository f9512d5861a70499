"""Mean time a request waited for its batch to form: the server's exact
form-wait total over its count, differenced across the window."""


def read(run):
    c = run.window.counters
    if not c.get("form_wait_n"):
        return None
    return c["form_wait_s"] / c["form_wait_n"] * 1e3
