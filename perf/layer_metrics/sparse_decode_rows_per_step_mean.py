"""Cache manager: rows a decode step actually carries — decode queries of
the layers with an indexer over the window (the program's
`sparse.decode_queries`, accumulated on the device inside the step
program) / those layers / the window's decode steps (the harness's step
records). A seat whose prompt still waits for its prefill chunks holds a
slot and decodes nothing, so this is the occupancy that
`batch_occupancy_mean` (seats taken) cannot see; tokens a second follow it
at a fixed cycle, and the decode program's row gather grows with it."""
from harness import counter_window, flops_latent_sparse


def rows_decoded(rec):
    """Tokens the window's decode steps emitted (one a query a layer),
    None without the program's selection counters."""
    d = counter_window.delta(rec)
    if not d or not d.get("sparse.decode_queries") \
            or "layer_types" not in rec.get("model", {}):
        return None
    full = sum(not windowed
               for windowed, _ in flops_latent_sparse.layers(rec["model"]))
    return d["sparse.decode_queries"] / full


def read(rec):
    rows = rows_decoded(rec)
    steps = sum(s[2] == "decode" for s in rec.get("steps", ()))
    return rows / steps if rows and steps else None
