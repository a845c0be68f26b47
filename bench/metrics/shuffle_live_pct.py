"""Live pairs as a share of the slots the shuffle walks, from the
``shuffle.pairs`` and ``shuffle.slots`` device counters of one run of the
mode's ``counters=True`` variant after the window."""

from bench.scopes import live_pct


def read(r):
    return live_pct(r.counters or {}).get("shuffle_live_pct")
