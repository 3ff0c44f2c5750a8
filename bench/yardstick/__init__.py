"""The benchmark's own library: cell lookup, the traffic generator, trace
reduction, operation counts, comparisons and the run itself.

Nothing here is imported by the program under test.  The system drivers
(``systems/``) import the program (``repro``) only to build and drive the
system under test; the yardstick itself -- traffic, references, counts,
peaks, reductions -- lives in this directory, where a PR that claims a gain
cannot change it.
"""
