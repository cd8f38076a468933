"""Entry / set-up: seconds jax spent obtaining executables (compiling, or
reading the persistent cache) before the window opened."""


def read(rec):
    return rec["clock"]["compile_s_setup"]
