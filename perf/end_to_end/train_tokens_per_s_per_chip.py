"""Tokens of the whole optimizer steps finished in the window, over the
window (idle device to the last loss ready), over the chips used."""


def read(rec):
    if rec["kind"] != "train" or not rec["n_steps"]:
        return None
    t0, t1 = rec["window"]
    return rec["n_steps"] * rec["tokens_per_step"] / (t1 - t0) / rec["chips"]
