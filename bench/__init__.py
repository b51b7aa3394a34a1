"""The chip benchmark: one command runs one cell of ``BENCHMARK.json`` once.

Everything that measures lives here: cell lookup, traffic generation,
trace reduction, operation and byte counts, the table of peaks, each
configuration's plain reference and the comparison that decides
``correct``.  From the program it takes only the system under test.
"""
