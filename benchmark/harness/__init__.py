"""The benchmark's yardstick: everything here is the benchmark's own.

From the program (``bigdl_tpu``) the harness takes the system under test
and its counters; traffic generation, metric arithmetic, the table of
peaks, operation and byte counts, the trace reduction, the plain
references and the comparison that decides ``correct`` live here.
"""
