"""Operations-and-bytes functions in files of their own: a metric's kernel
or a configuration's ``flops`` names one as ``<module>.<function>``
(``manifest.count_function``), so a later PR adds a count function by
adding a file here. Like ``flops.py``'s, each returns what the work
REQUIRES, from sizes alone, and is checked against a count worked out by
hand in the benchmark's tests."""
