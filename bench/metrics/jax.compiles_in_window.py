"""Executables compiled, or loaded from the persistent cache, inside the
window, from JAX's own monitoring events (``bench/counters.py``).  Set-up
warms up every shape, so it should read 0."""


def read(records):
    return records.compiles_in_window
