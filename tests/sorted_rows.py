"""Sorted (key, value) rows for the segment-aggregation tests, and a plain
numpy group-by that states what aggregating them must give.

Each case is an (N, C) int32 block in the reduce backends' layout: every
row sorted by key, dead slots holding ``PAD_KEY`` at the end.  The
reference works key by key and never looks at another implementation.
"""

import numpy as np

from repro.mapreduce import PAD_KEY
from repro.mapreduce.phases import SCAN_BLOCK

#: row width: more than two scan blocks and not a multiple of one, so runs
#: cross block boundaries and the last block is padded
C = 2 * SCAN_BLOCK + 44
I32 = np.iinfo(np.int32)
OPS = ("sum", "max", "first")


def _full_range(rng, shape):
    return rng.integers(I32.min, I32.max, size=shape, endpoint=True,
                        dtype=np.int64).astype(np.int32)


def _sorted_row(rng, live: int, distinct: int):
    keys = np.full(C, PAD_KEY, np.int32)
    keys[:live] = np.sort(rng.integers(0, distinct, size=live))
    return keys


def case(name: str):
    """(keys, values) of the case ``name`` (one of ``CASES``)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "int32_wrap":
        # Few keys, long runs, values over the whole int32 range: sums wrap.
        keys = np.stack([_sorted_row(rng, live, 4) for live in (C, 200, 5)])
        return keys, _full_range(rng, keys.shape)
    if name == "all_pad":
        keys = np.stack([np.full(C, PAD_KEY, np.int32),
                         _sorted_row(rng, 150, 3)])
        return keys, _full_range(rng, keys.shape)
    if name == "one_run":
        keys = np.full((2, C), 7, np.int32)
        keys[1] = 123456
        return keys, _full_range(rng, keys.shape)
    if name == "singletons":
        keys = np.full((2, C), PAD_KEY, np.int32)
        keys[0] = np.arange(C) * 3 - 20
        keys[1, :140] = np.arange(140)
        return keys, _full_range(rng, keys.shape)
    if name == "pad_garbage":
        # Dead slots hold values that would poison a sum or a max.
        keys = np.stack([_sorted_row(rng, 170, 4), _sorted_row(rng, 3, 2)])
        values = rng.integers(-50, 50, size=keys.shape).astype(np.int32)
        dead = keys == PAD_KEY
        values[dead] = np.resize([I32.max, I32.min, 99, -1], dead.sum())
        return keys, values
    raise KeyError(name)


CASES = ("int32_wrap", "all_pad", "one_run", "singletons", "pad_garbage")


def _aggregate(vals, op: str) -> int:
    if op == "sum":  # int32 addition wraps
        return int((int(np.sum(vals, dtype=np.int64)) + 2**31) % 2**32
                   - 2**31)
    if op == "max":
        return int(np.max(vals))
    return int(vals[0])  # first: the earliest value of the run


def group_by_row(keys, values, op: str):
    """One sorted row aggregated per key: (out_keys, out_vals, first), the
    aggregate at each key's first slot and (PAD_KEY, 0) elsewhere."""
    out_k = np.full_like(keys, PAD_KEY)
    out_v = np.zeros_like(values)
    first = np.zeros(keys.shape, bool)
    live = keys != PAD_KEY
    uniq, at = np.unique(keys[live], return_index=True)
    for key, i in zip(uniq, at):
        out_k[i], first[i] = key, True
        out_v[i] = _aggregate(values[live][keys[live] == key], op)
    return out_k, out_v, first


def group_by(keys, values, op: str):
    """``group_by_row`` over every row of a block: (out_keys, out_vals)."""
    rows = [group_by_row(k, v, op)[:2] for k, v in zip(keys, values)]
    return np.stack([r[0] for r in rows]), np.stack([r[1] for r in rows])


def compacted(keys, values, op: str):
    """The combine's layout: each row's aggregates front-packed in
    ascending key order, then a (PAD_KEY, 0) tail."""
    out_k, out_v = group_by(keys, values, op)
    order = np.argsort(out_k, axis=1, kind="stable")
    return (np.take_along_axis(out_k, order, axis=1),
            np.take_along_axis(out_v, order, axis=1))
