"""The benchmark: BENCHMARK.json's command, its yardstick and its data files.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the reduction from traces, spans and
counters to metrics, the table of peaks, the operations-and-bytes functions
and the comparison that decides ``correct``. From the program it takes the
system under test and its spans, counters and kernel names. README.md says
how a later PR adds a configuration, a traffic mix, a metric or a cell as
files and entries only.
"""
