"""Entry / set-up: executables the persistent compilation cache did not
hold (0 on a warm cache)."""


def read(rec):
    return rec["clock"]["cache_misses"]
