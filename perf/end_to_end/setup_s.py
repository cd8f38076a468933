"""Process start to the opening of the measured window: loading,
building, the correctness check, warm-up and, in a run that compiles,
compilation."""


def read(rec):
    return rec["window"][0] - rec["t_process_start"]
