"""Cache manager: mean over the window's steps of the FULL page groups'
pages in use over the pages they have (the program's per-group page
counters). Grows with context: the full layers keep every token."""
from harness import counter_window


def read(rec):
    return counter_window.group_used_share(rec, windowed=False)
