"""Live pairs as a share of the slots the reduce walks, from the
``reduce.pairs`` and ``reduce.slots`` device counters of one run of the
mode's ``counters=True`` variant after the window."""

from bench.scopes import live_pct


def read(r):
    return live_pct(r.counters or {}).get("reduce_live_pct")
