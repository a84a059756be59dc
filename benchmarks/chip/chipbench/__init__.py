"""The on-chip benchmark's yardstick: cells, work counts, peaks, trace
reduction, the plain reference and the comparison that decides ``correct``.

Nothing here imports the program under test except ``harness``, which
drives it.
"""
