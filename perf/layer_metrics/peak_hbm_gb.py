"""Training step: peak bytes in use on the fullest chip over the whole
process (memory_stats), in GB of 1e9 bytes."""


def read(rec):
    peak = rec["device"].get("memory_peak_bytes")
    return None if not peak else peak / 1e9
