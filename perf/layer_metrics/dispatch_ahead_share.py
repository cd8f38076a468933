"""Scheduler host loop: programs dispatched while the one before them was
still unresolved (its tokens not yet fetched and booked), over the
window's steps (the program's `ahead.dispatched` and `steps` counters).
Near 1.0 the device always has the next program queued behind the one it
runs; 0.0 is the resolve-first order (every step fetches its own tokens
before the next is dispatched); None where the program keeps no such
counter (the parent commit)."""
from harness import counter_window


def read(rec):
    d = counter_window.delta(rec)
    if not d or not d.get("steps") or "ahead.dispatched" not in d:
        return None
    return d["ahead.dispatched"] / d["steps"]
