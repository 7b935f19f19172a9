"""chipbench: the benchmark of the served SQL path on the chip.

The yardstick (traffic, statistics, trace reduction, peaks, plain
references, the comparison that decides `correct`) lives here, where a
PR that claims a gain cannot change it. From the program it takes only
the system under test and its counters. See README.md.
"""
