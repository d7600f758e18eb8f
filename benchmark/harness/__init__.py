"""The yardstick: cell lookup, token generator, window driver, trace
reduction, peaks and FLOP/byte counts, the comparison that decides
``correct``.  Only ``program.py`` imports the system under test."""
