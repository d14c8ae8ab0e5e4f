"""A count the run took: ``compiles_in_window`` (programs built between
the window's start and the end of the drain; expected 0), or one the
driver read from the program's state after the window."""


def read(run, key: str):
    return run.rec["counters"].get(key)
