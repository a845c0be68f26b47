"""Pallas TPU sorted segment-reduce kernel (MapReduce reduce-task combine).

One reduce-task partition per grid step: the engine hands each reducer a
capacity-bounded, key-sorted partition; the kernel aggregates equal-key runs
entirely in VMEM.

TPU adaptation: no scatter.  The scatter-style segment sum of the XLA
reference becomes a matmul against a one-hot segment matrix — MXU work
instead of serial VREG updates:

    onehot_t[s, i] = (seg_id[i] == s)             (C x C, built from iota)
    agg  = values @ onehot_t^T                    (segment sums)
    back = agg @ onehot_t                         (scatter-back, again MXU)

Segment ids are an inclusive prefix sum of the first-occurrence mask.  A
prefix sum has no Pallas TPU lowering, so :func:`segment_ids` computes it
in XLA, ahead of the kernel; the kernel does the one-hot segment sums, and
the first-occurrence masking is XLA again.  Rows are laid out as
(N, 1, C) and C padded to a multiple of 128, so every block's last two
dimensions are (1, C) — equal to the array's — and lane-aligned, which is
what the TPU compiler requires of a block.

The one-hot is (C, C) float32 in VMEM, so C is bounded: :data:`MAX_C`.
Both kernels (this one and ``local_reduce``) share :func:`onehot_segment_sums`.

Grid: (N,); blocks: seg (1, C), lhs (k, C) -> out (k, C).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAD_KEY = jnp.iinfo(jnp.int32).max
LANES = 128
#: largest partition width (after padding to a multiple of 128) the
#: kernels accept: the (C, C) float32 one-hot and its iota must fit VMEM.
#: ``tests/test_tpu_compile.py`` compiles both kernels at this width.
MAX_C = 2048
#: scoped-VMEM request covering the one-hot temporaries at MAX_C
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def padded_width(C: int) -> int:
    """C rounded up to the lane width; raises past :data:`MAX_C`."""
    Cp = -(-C // LANES) * LANES
    if Cp > MAX_C:
        raise ValueError(
            f"pallas segment kernels support partition width C <= {MAX_C} "
            f"(MAX_C; the (C, C) one-hot must fit VMEM), got C={C}; use "
            f"the 'jnp' or 'xla' reduce backend for wider partitions"
        )
    return Cp


def pad_lanes(x, Cp: int, fill):
    """Pad the last axis of (N, C) ``x`` to ``Cp`` with ``fill``."""
    C = x.shape[-1]
    if C == Cp:
        return x
    return jnp.pad(x, ((0, 0), (0, Cp - C)), constant_values=fill)


def segment_ids(keys):
    """XLA prologue: (first, seg) for (N, C) key-sorted rows.

    ``first`` marks each live equal-key run's first slot; ``seg`` numbers
    the runs 0, 1, ... along the row and is -1 on PAD slots (so a PAD slot
    matches no one-hot row and contributes nothing).
    """
    valid = keys != PAD_KEY
    first = jnp.concatenate(
        [jnp.ones_like(keys[:, :1], bool), keys[:, 1:] != keys[:, :-1]],
        axis=1,
    ) & valid
    seg = jnp.cumsum(first.astype(jnp.int32), axis=1) - 1
    return first, jnp.where(valid, seg, -1)


def _kernel(seg_ref, lhs_ref, out_ref, *, scatter_back: bool):
    seg = seg_ref[...]                                   # (1, C)
    C = seg.shape[-1]
    s = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    onehot_t = (seg == s).astype(jnp.float32)            # (s, i)
    agg = jax.lax.dot_general(                           # (k, C_s) sums
        lhs_ref[...], onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if scatter_back:                                     # agg[seg[i]]
        agg = jax.lax.dot_general(
            agg, onehot_t, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    out_ref[...] = agg


def onehot_segment_sums(seg, lhs, *, scatter_back: bool,
                        interpret: bool = True):
    """seg (N, C) int32 segment ids (-1 = none), lhs (N, k, C) float32.

    Returns (N, k, C) float32: per row, the sums of each lhs row over each
    segment at slot = segment id, or (``scatter_back``) each slot's own
    segment sum.  C must already be a multiple of 128 and <= MAX_C.
    """
    N, k, C = lhs.shape
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, scatter_back=scatter_back),
        grid=(N,),
        in_specs=[
            pl.BlockSpec((None, 1, C), lambda r: (r, 0, 0)),
            pl.BlockSpec((None, k, C), lambda r: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, k, C), lambda r: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, k, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(seg[:, None, :], lhs)


def segment_reduce_fwd(keys, values, *, interpret: bool = True):
    """keys (R, C) int32 per-partition sorted; values (R, C) float32.

    Returns (out_k, out_v): each run's aggregate at its first occurrence,
    (PAD_KEY, 0) elsewhere.
    """
    C = keys.shape[1]
    Cp = padded_width(C)
    keys = pad_lanes(keys, Cp, PAD_KEY)
    first, seg = segment_ids(keys)
    vals = jnp.where(seg >= 0, pad_lanes(values, Cp, 0.0), 0.0)
    back = onehot_segment_sums(
        seg, vals[:, None, :], scatter_back=True, interpret=interpret
    )[:, 0, :]
    out_k = jnp.where(first, keys, PAD_KEY)[:, :C]
    out_v = jnp.where(first, back, 0.0)[:, :C]
    return out_k, out_v
