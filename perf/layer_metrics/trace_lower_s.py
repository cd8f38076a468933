"""Entry / set-up: seconds jax spent tracing python to jaxprs and lowering
them to MLIR over the whole run — the part of set-up no compilation cache
saves, since a cache key needs the lowered module."""


def read(rec):
    return rec["clock"]["trace_lower_s"]
