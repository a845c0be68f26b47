"""Live pairs as a share of the pair slots the map emits and spill-sorts,
from the ``map.pairs`` and ``map.slots`` device counters of one run of the
mode's ``counters=True`` variant after the window; None where the program
counts no ``map.slots``."""


def read(r):
    counters = r.counters or {}
    if not counters.get("map.slots"):
        return None
    return 100.0 * counters["map.pairs"] / counters["map.slots"]
