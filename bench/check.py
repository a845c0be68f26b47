"""The comparison that decides ``correct``, and the control that breaks it.

The program's output is a pair of ``(R, cap)`` int32 arrays, keys and
values, with unused slots holding the key ``PAD`` (int32 max).  The
configurations state one guarantee: every key's aggregate is exact, each
key is emitted once, and no pair is dropped.  So the comparison is exact
and its limits are 0.
"""

from __future__ import annotations

import numpy as np

PAD = np.iinfo(np.int32).max

#: Each compared number and its limit.  An exact comparison has limit 0.
LIMITS = {"wrong_keys": 0, "dropped": 0, "jobs_differing": 0}


def exact(keys, values, key_space: int):
    """Plain reference: per-key pair count and exact sum, dense over
    ``key_space`` (numpy, int64)."""
    keys = np.asarray(keys, np.int64).ravel()
    values = np.asarray(values, np.int64).ravel()
    counts = np.bincount(keys, minlength=key_space)
    # float64 holds every partial sum exactly below 2^53.
    sums = np.bincount(keys, weights=values, minlength=key_space)
    return counts, np.rint(sums).astype(np.int64)


def wrong_keys_dense(present, sums, ref_counts, ref_sums) -> int:
    """Keys present on one side only, plus keys whose aggregate differs."""
    ref_present = ref_counts > 0
    both = present & ref_present
    return int((present != ref_present).sum()
               + (sums[both] != ref_sums[both]).sum())


def wrong_keys(out_keys, out_vals, ref_counts, ref_sums) -> int:
    """Compare a job's whole output with the reference.  A key emitted
    twice, or outside the key space, counts as wrong too."""
    key_space = ref_counts.shape[0]
    keys = np.asarray(out_keys).ravel()
    vals = np.asarray(out_vals).ravel()
    live = keys != PAD
    k, v = keys[live].astype(np.int64), vals[live].astype(np.int64)
    inside = (k >= 0) & (k < key_space)
    emitted = np.bincount(k[inside], minlength=key_space)
    sums = np.zeros(key_space, np.int64)
    sums[k[inside]] = v[inside]
    return (int((~inside).sum()) + int((emitted > 1).sum())
            + wrong_keys_dense(emitted > 0, sums, ref_counts, ref_sums))


def fingerprint(out_keys, out_vals):
    """A position-weighted uint32 hash of one job's output, computed on
    the device, so every job of the window can be compared with the one
    that is checked in full without copying its output to the host."""
    import jax.numpy as jnp

    k = out_keys.astype(jnp.uint32).ravel()
    v = out_vals.astype(jnp.uint32).ravel()
    i = jnp.arange(k.shape[0], dtype=jnp.uint32)
    h = (k * jnp.uint32(0x9E3779B1) + v * jnp.uint32(0x85EBCA77)) \
        ^ (i * jnp.uint32(0xC2B2AE3D) + jnp.uint32(0x27D4EB2F))
    return jnp.sum(h, dtype=jnp.uint32)


def control(keys, values, key_space: int):
    """The control: the reference's aggregation put in the program's place
    with its values and sums carried in bfloat16, a narrowing that breaks
    the exact-aggregate guarantee.  Runs on the device; returns dense
    ``(present, sums)`` on the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def agg(keys, values):
        ones = jnp.ones(keys.shape, jnp.bfloat16)
        cnt = jax.ops.segment_sum(ones, keys, num_segments=key_space)
        s = jax.ops.segment_sum(values.astype(jnp.bfloat16), keys,
                                num_segments=key_space)
        return cnt > 0, s.astype(jnp.float32)

    present, sums = agg(keys, values)
    return np.asarray(present), np.rint(np.asarray(sums)).astype(np.int64)
