"""Entry / set-up: seconds inside the program's `setup.engine` span, the
whole of `ContinuousBatchingEngine.__init__` (weight snapshot and int8
quantization, page pool, megakernel pack), from the program's always-on
`span_totals()`. Host time: work it left running on the device is not
in it."""
from harness import span_reduce


def read(rec):
    totals = span_reduce.program_totals()
    if not totals or "setup.engine" not in totals:
        return None
    return totals["setup.engine"][1]
