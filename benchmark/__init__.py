"""The benchmark: BENCHMARK.json's command, its data files and its yardstick.

Everything a later PR may not change lives here: traffic generation, the
reduction from traces and samples to metrics, the table of peaks, the
operation and byte counts, the plain reference and the comparison behind
``correct``. From the program it takes the system under test only.
"""
