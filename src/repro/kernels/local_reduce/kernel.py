"""Pallas TPU local-reduce kernel (MapReduce map-side combine).

One map task's spill-sorted pair row per grid step: aggregate equal-key
runs *and* front-pack the aggregates, so the combined row can be
truncated to the task's distinct-key bound before it reaches the shuffle
fabric.

TPU adaptation: the same one-hot segment matmul as ``segment_reduce``
(shared: :func:`repro.kernels.segment_reduce.kernel.onehot_segment_sums`),
but the output stays indexed by *segment id* instead of being scattered
back to first-occurrence positions, which IS the compaction (segment ids
are dense in 0..n_segments-1 because the row is sorted).  Four rows ride
one MXU pass:

    values          -> agg[s]   compacted segment sums
    first * lo16(k) -> lo[s]    low 16 key bits of segment s
    first * hi16(k) -> hi[s]    high 16 key bits of segment s
    first           -> occ[s]   1 where segment s exists

Each segment has exactly one first occurrence, so ``lo``/``hi`` are that
key's halves — exact in float32, where a whole int32 key (PAD_KEY alone is
2**31 - 1) would not be — and the key is reassembled in int32 afterwards.

Grid: (n_tasks,); blocks: seg (1, C), lhs (8, C) -> out (8, C).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce.kernel import (
    PAD_KEY,
    onehot_segment_sums,
    pad_lanes,
    padded_width,
    segment_ids,
)

_SUBLANES = 8  # lhs rows padded to one full (8, 128) tile


def local_reduce_fwd(keys, values, *, interpret: bool = True):
    """keys (N, C) int32 per-task spill-sorted rows; values (N, C) float32.
    Returns (out_k, out_v) of the same shape with each row's equal-key
    aggregates front-packed in ascending key order, (PAD_KEY, 0) tail."""
    N, C = keys.shape
    Cp = padded_width(C)
    keys = pad_lanes(keys, Cp, PAD_KEY)
    first, seg = segment_ids(keys)
    f = first.astype(jnp.float32)
    bits = keys.astype(jnp.uint32)
    lo = (bits & 0xFFFF).astype(jnp.float32)
    hi = (bits >> 16).astype(jnp.float32)
    vals = jnp.where(seg >= 0, pad_lanes(values, Cp, 0.0), 0.0)
    rows = [vals, f * lo, f * hi, f]
    zeros = jnp.zeros_like(vals)
    lhs = jnp.stack(rows + [zeros] * (_SUBLANES - len(rows)), axis=1)
    out = onehot_segment_sums(seg, lhs, scatter_back=False,
                              interpret=interpret)
    agg, lo_s, hi_s, occ = (out[:, j, :C] for j in range(4))
    key = (hi_s.astype(jnp.uint32) << 16) | lo_s.astype(jnp.uint32)
    live = occ > 0
    out_k = jnp.where(live, key.astype(jnp.int32), PAD_KEY)
    out_v = jnp.where(live, agg, 0.0)
    return out_k, out_v
