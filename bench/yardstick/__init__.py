"""The benchmark's own yardstick: inputs from the seed, the plain reference of
the online OSAFL round, the comparison that decides ``correct``, and the
reduction from a profiler trace to metrics. Nothing here imports the program
under test (``src/repro``)."""
