"""Cache manager: keys attended over keys visible, over the window's
decode queries of the layers with an indexer (the program's selection
counters, accumulated on the device inside the step program). 1.0 while
every context is under `index_topk`; index_topk / context beyond."""
from harness import counter_window


def read(rec):
    d = counter_window.delta(rec)
    if not d or not d.get("sparse.keys_visible"):
        return None
    return d["sparse.keys_attended"] / d["sparse.keys_visible"]
