"""Entry / set-up: executables obtained (compiled or read from the cache)
inside the measured window. Must be 0: anything else is a shape the
warm-up missed, and its cost sits in the window."""


def read(rec):
    return len(rec["clock"]["in_window"])
