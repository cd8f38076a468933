"""Output tokens the engine delivered inside the window (difference of
its public emitted-token counter between the two ends), over the window."""


def read(rec):
    if rec["kind"] != "serve" or not rec["tokens_in_window"]:
        return None
    t0, t1 = rec["window"]
    return rec["tokens_in_window"] / (t1 - t0)
