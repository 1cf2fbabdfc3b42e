"""Shared pieces of the chip benchmark: cell lookup, device check, peaks,
algorithmic counts, traffic generation, statistics and tracing.

Nothing here imports the program under test (``src/repro``) except
``faults.py``, which plants faults in it for the tests and the
calibration runs.  The drivers in ``bench/drivers/`` import the program,
and only the system they measure.
"""
