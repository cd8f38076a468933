"""Cache manager: mean over the window's steps of the WINDOW page
groups' pages in use over the pages they have (the program's per-group
page counters). Bounded whatever the context: pages behind the window
are freed."""
from harness import counter_window


def read(rec):
    return counter_window.group_used_share(rec, windowed=True)
