"""The benchmark's yardstick: device checks, inputs, trace reduction, reference.

Nothing here imports the parser (``src/repro``) except ``cells.py``, which
builds the system under test from a configuration file.
"""
