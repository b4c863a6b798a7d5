"""Lowerings (``jax.monitoring``) inside the window: each is a program built
or fetched from the persistent cache.  Must read 0."""


def read(sample):
    return float(sample["compiles_in_window"])
