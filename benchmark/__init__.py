"""The repo's benchmark: harness, data and yardstick (see README.md).

Everything the driver's check runs lives under this directory and
``tests/benchmark_harness``; from the program it takes only the system
under test and its spans, counters and kernel names.
"""
