"""Cache manager: rows a decode step actually carries in the per-head
sparse block — the program's `sparse.decode_queries` over the window /
the configuration's `num_hidden_layers` (every layer has an indexer) /
the window's decode steps (the harness's step records): the seats that
DECODE, which `batch_occupancy_mean` (seats taken) cannot see.
`sparse_decode_rows_per_step_mean` reads the same counter but counts the
indexer layers from a `layer_types` list this family has not (PERF.md
7.8: a `benchmark` PR should make the two one)."""
from harness import counter_window


def read(rec):
    d = counter_window.delta(rec)
    model = rec.get("model", {})
    if not d or not d.get("sparse.decode_queries") \
            or "sa_config" not in model:
        return None
    steps = sum(s[2] == "decode" for s in rec.get("steps", ()))
    if not steps:
        return None
    return d["sparse.decode_queries"] / model["num_hidden_layers"] / steps
