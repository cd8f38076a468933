"""Entry / set-up: seconds inside the program's `setup.first_call` spans,
one around the first call of every step program the engine built: python
tracing, lowering, the executable from the cache or the compiler, the
first run. From the program's always-on `span_totals()`; the number of
programs goes to stderr."""
import sys

from harness import span_reduce


def read(rec):
    totals = span_reduce.program_totals()
    if not totals or "setup.first_call" not in totals:
        return None
    count, seconds = totals["setup.first_call"]
    print(f"[perf] setup.first_call: {count} programs, {seconds:.3f} s",
          file=sys.stderr, flush=True)
    return seconds
