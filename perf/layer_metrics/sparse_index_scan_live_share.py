"""Cache manager: the share of the index keys a decode step's scan scores
that any query can see — `sparse.keys_visible` / `sparse.index_keys_
scored` over the window (the program's selection counters). The scan
gathers every table page of every slot of the step's bucket, so at a mean
context of a third of `max_len` two thirds of what it reads is dead; a
scan over live pages only reads 1.0 here."""
from harness import counter_window


def read(rec):
    d = counter_window.delta(rec)
    if not d or not d.get("sparse.index_keys_scored"):
        return None
    return d["sparse.keys_visible"] / d["sparse.index_keys_scored"]
