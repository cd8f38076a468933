"""The benchmark's yardstick: everything here is the benchmark's own and
takes nothing from the program but the system under test, its public
counters and its kernel names. See perf/README.md."""
