"""Collectives, mesh: device time in all-reduce / all-gather /
reduce-scatter / collective-permute / all-to-all operations over the
traced window, on device 0, by the operation's opcode. Time hidden behind
compute counts too: what is exposed is the next tracing issue's."""


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "train" or rec["chips"] < 2:
        return None
    return tr["collective_s"] / tr["window_s"]
