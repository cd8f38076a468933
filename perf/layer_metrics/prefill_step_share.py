"""Step programs: share of the traced window the device spent in
prefill-chunk programs (module `prefill`, device 0). Every one of them
stalls all decoding slots for its length."""


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve":
        return None
    return sum(tr["modules"].get("jit_prefill", [])) / tr["window_s"]
