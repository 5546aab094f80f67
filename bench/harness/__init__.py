"""The benchmark's own code: layout lookup, traffic generation, the plain
reference, the scheme and byte arithmetic, the peak table and the trace
reduction.  Nothing here imports the program (``src/repro``) except
``cell.py``, which drives the system under test."""
