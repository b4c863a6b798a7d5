"""The chip benchmark's own code: everything a later PR may not change.

``run.py`` runs one cell once.  What belongs to one configuration, one cell,
one consumer or one per-layer metric is a file found by its name (``spec.py``);
the modules here are the general parts: traffic generation, the load
generator, counter deltas, the trace reduction, the peaks table.
"""
