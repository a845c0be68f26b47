"""Shared MapReduce phase primitives (the single source of truth).

The engine used to carry two near-identical copies of the map-task,
combiner, partition, and reduce logic — one in ``build_job`` and one in
``build_job_sharded``.  This module is the one implementation both paths
(and any future backend) compose:

* :func:`task_setup`        — fixed per-task startup compute (JVM analogue);
* :func:`hash_to_reducer`   — Knuth multiplicative key hashing;
* :func:`segment_sum_sorted`— sorted equal-key aggregation (sum / max / first);
* :func:`run_map_task`      — setup + ``map_fn`` + local spill sort;
* :func:`map_phase`         — wave-scheduled map over (waves, W) task grid;
* :func:`combine_rows`      — map-side combine: per-task aggregation +
  compaction of the spill-sorted rows, shrinking everything downstream
  (:func:`combine_capacity` is the static distinct-key bound);
* :func:`bucket_scatter`    — capacity-bounded partition scatter, with
  overflow *accounting* (the ``dropped`` count) instead of silent loss;
* :func:`reduce_phase` / :func:`reduce_local` — wave-scheduled reduce
  through a pluggable :class:`repro.mapreduce.backends.ReduceBackend`;
* :func:`scoped`            — a phase function under its named scope
  ``mr.<phase>``, so a profile of any program built from it can say which
  phase each device op belongs to;
* :func:`pair_counts` / :func:`shuffle_counts` / :func:`partition_counts` /
  :func:`output_counts` — the device counters at each phase boundary.

Everything is pure ``jnp`` with static shapes, so every phase composes
under ``jit``, ``vmap``, ``scan``, and ``shard_map``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PAD_KEY = jnp.iinfo(jnp.int32).max  # sorts to the end

#: bytes per (key, value) pair moving between phases: two int32s.  The
#: telemetry layer's byte counters (shuffle bytes_in/out/dropped) are pair
#: counts scaled by this, so conservation in pairs and bytes coincide.
PAIR_BYTES = 8

#: reduce ops safe to pre-aggregate map-side: a combiner applies the op
#: twice (per task, then per reducer), which is only semantics-preserving
#: for commutative + associative ops.  ``first`` keeps the earliest value
#: per key in shuffle-delivery order, so combining it would change which
#: value survives — the plan rejects combiner configs for it.
COMBINABLE_OPS = ("sum", "max")


#: prefix of the named scope every phase runs under (``mr.map``,
#: ``mr.combine``, ``mr.shuffle``, ``mr.reduce``): the ``op_name`` metadata
#: of each HLO instruction a phase emits holds ``mr.<phase>``
SCOPE = "mr"


def count_live(keys) -> jnp.ndarray:
    """Number of live (non-PAD) slots in a key array — the counter primitive
    shared by the telemetry layer and the conservation tests."""
    return (jnp.asarray(keys) != PAD_KEY).sum()


def scoped(phase: str, fn):
    """``fn`` traced under ``jax.named_scope("mr.<phase>")``.  A scope is
    metadata only: the program runs the same operations without it."""
    name = f"{SCOPE}.{phase}"

    @functools.wraps(fn)
    def run(*args):
        with jax.named_scope(name):
            return fn(*args)

    return run


# Device counters, one function per phase boundary.  Each returns int32
# device scalars keyed ``<phase>.<counter>``, so a job reduces them on the
# device and only scalars reach the host.


def _total(x) -> jnp.ndarray:
    return jnp.sum(x, dtype=jnp.int32)


def pair_counts(phase: str, pvalid) -> dict:
    """Out of the map or the combine: ``<phase>.pairs``, the live pairs
    the phase emits."""
    return {f"{phase}.pairs": _total(pvalid)}


def shuffle_counts(keys, pvalid, dropped) -> dict:
    """Through the shuffle: the pair slots it sorts (live or not), the
    live pairs among them, and the pairs it dropped."""
    return {
        "shuffle.slots": jnp.int32(keys.size),
        "shuffle.pairs": _total(pvalid),
        "shuffle.dropped": _total(dropped),
    }


def partition_counts(part_keys) -> dict:
    """Into the reduce: the partition slots it walks (R x cap) and the live
    (non-PAD) pairs in them."""
    return {
        "reduce.slots": jnp.int32(part_keys.size),
        "reduce.pairs": _total(part_keys != PAD_KEY),
    }


def output_counts(out_keys) -> dict:
    """Out of the reduce: the live output segments, one per key."""
    return {"reduce.segments": _total(out_keys != PAD_KEY)}


def task_setup(dim: int, rounds: int, seed_val):
    """Fixed per-task startup compute — the JVM-start analogue.

    A short chain of (dim x dim) matmuls seeded by the task's data so XLA
    cannot fold it away.  Cost is independent of split size: pure overhead.
    """
    x = (
        jnp.full((dim, dim), 1e-3, dtype=jnp.float32)
        + seed_val.astype(jnp.float32) * 1e-9
    )
    w = jnp.eye(dim, dtype=jnp.float32) * 0.999

    def body(x, _):
        return jnp.tanh(x @ w), None

    x, _ = jax.lax.scan(body, x, None, length=rounds)
    return x.sum() * 1e-20  # ~0 but data-dependent; folded into values


def hash_to_reducer(keys, num_reducers: int):
    """Knuth multiplicative hash in uint32, then mod R."""
    h = keys.astype(jnp.uint32) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    return (h % jnp.uint32(num_reducers)).astype(jnp.int32)


def segment_sum_sorted(keys, values, valid, reduce_op: str = "sum"):
    """Aggregate values of equal adjacent keys (input sorted by key).

    Returns (unique_keys, aggregated, out_valid): one slot per first
    occurrence, PAD elsewhere.  Pure jnp; the Pallas `segment_reduce` kernel
    implements the same contract for the TPU deployment path.
    """
    n = keys.shape[0]
    first = jnp.concatenate(
        [jnp.array([True]), keys[1:] != keys[:-1]]
    ) & valid
    seg_id = jnp.cumsum(first.astype(jnp.int32)) - 1  # -1 before first valid
    seg_id = jnp.where(valid, seg_id, n - 1)  # dump invalid into last slot
    if reduce_op == "sum":
        agg = jnp.zeros((n,), dtype=values.dtype).at[seg_id].add(
            jnp.where(valid, values, 0)
        )
    elif reduce_op == "max":
        agg = jnp.full((n,), jnp.iinfo(jnp.int32).min, dtype=values.dtype)
        agg = agg.at[seg_id].max(
            jnp.where(valid, values, jnp.iinfo(jnp.int32).min)
        )
    elif reduce_op == "first":
        # The earliest value of each run in delivery order: the stable
        # sorts upstream put it at the first-occurrence slot, so the
        # aggregate IS the value already sitting there.  Order-dependent
        # by definition — hence not in COMBINABLE_OPS.
        agg = jnp.zeros((n,), dtype=values.dtype).at[seg_id].add(
            jnp.where(first, values, 0)
        )
    else:
        raise ValueError(reduce_op)
    # The aggregate for the segment starting at a first-occurrence position i
    # is agg[seg_id[i]]; non-first slots are PAD.
    out_keys = jnp.where(first, keys, PAD_KEY)
    out_vals = jnp.where(first, agg[seg_id], 0)
    return out_keys, out_vals, first


def run_map_task(app, cfg, tokens, valid):
    """One map task: startup + map_fn + local spill sort.

    tokens/valid: (S,).  Returns keys/values/pvalid of shape (P,).  The
    map-side combiner is *not* applied here — it is its own fenced stage
    (:func:`combine_rows`, run by the plan between map and shuffle) so it
    can be wall-clocked, counted, and checkpointed at a wave boundary.
    """
    setup = task_setup(cfg.setup_dim, cfg.setup_rounds, tokens.sum())
    keys, values, pvalid = app.map_fn(tokens, valid)
    # Local spill sort (Hadoop sorts map output before the shuffle).
    order = jnp.argsort(jnp.where(pvalid, keys, PAD_KEY))
    keys, values, pvalid = keys[order], values[order], pvalid[order]
    values = values + setup.astype(values.dtype)  # keep setup live
    return keys, values, pvalid


def map_phase(app, cfg, splits, split_valid):
    """Run map tasks in waves of W workers.

    splits: (waves, W, S) int32; split_valid: (waves, W, S) bool.
    Returns keys/values/valid of shape (waves, W, P).
    """

    def wave(carry, inp):
        tok, val = inp
        k, v, pv = jax.vmap(lambda t, m: run_map_task(app, cfg, t, m))(
            tok, val
        )
        return carry, (k, v, pv)

    _, (keys, values, pvalid) = jax.lax.scan(
        wave, jnp.int32(0), (splits, split_valid)
    )
    return keys, values, pvalid


def partition_capacity(n_pairs: int, n_buckets: int, factor: float) -> int:
    """Capacity per partition: uniform share x safety factor, clamped."""
    cap = max(1, int(math.ceil(n_pairs / max(n_buckets, 1) * factor)))
    return min(cap, n_pairs)


def combine_capacity(n_pairs: int, key_space: int) -> int:
    """Static per-task combined-row width: a task emitting ``n_pairs``
    pairs over ``key_space`` possible keys produces at most
    ``min(n_pairs, key_space)`` distinct keys, so truncating the combined
    row there is lossless — and it is this *static* shrink that pulls
    every downstream capacity (:func:`partition_capacity` feeds on the
    stream width) down with it."""
    return max(1, min(int(n_pairs), int(key_space)))


def combine_rows(backend, keys, values, pvalid, reduce_op: str, cap: int):
    """Map-side combine over task-major rows: aggregate each task's
    equal-key runs and compact the row to ``cap`` columns.

    keys/values/pvalid: (N, P) spill-sorted task rows.  Dead slots may
    hold garbage keys (the spill sort only orders by the masked view), so
    they are first masked to PAD_KEY — the validity contract of
    :class:`repro.mapreduce.backends.ReduceBackend`.  The backend's
    ``combine`` front-packs each row's aggregates in ascending key order;
    the static ``[:cap]`` truncation (``cap`` from
    :func:`combine_capacity`) then drops only dead tail slots.

    Returns (ck, cv, cvalid) of shape (N, cap).
    """
    km = jnp.where(pvalid, keys, PAD_KEY)
    vm = jnp.where(pvalid, values, 0)
    ck, cv = backend.combine(km, vm, reduce_op)
    ck, cv = ck[:, :cap], cv[:, :cap]
    return ck, cv, ck != PAD_KEY


def bucket_scatter(ids, n_buckets, n_rows, cap, arrays, fills):
    """Capacity-bounded scatter into fixed (n_rows, cap) partitions.

    ids: (n,) int32, **sorted ascending**; values >= n_buckets mark invalid
    entries (they land nowhere).  ``arrays`` are parallel (n,) arrays; each
    is scattered to ``out[id, position-within-bucket]``, initialised to its
    ``fills`` entry.  Rows n_buckets..n_rows stay at fill (wave padding).

    Returns (list of (n_rows, cap) arrays, dropped) where ``dropped`` counts
    valid entries lost to capacity overflow — Hadoop's fixed spill/partition
    buffers, but with the loss *accounted* so tests can assert conservation.
    """
    n = ids.shape[0]
    start = jnp.searchsorted(ids, jnp.arange(n_buckets + 1), side="left")
    pos = jnp.arange(n) - start[jnp.clip(ids, 0, n_buckets)]
    valid = ids < n_buckets
    dropped = jnp.sum((pos >= cap) & valid)
    row = jnp.where(valid & (pos < cap), ids, n_rows)
    col = jnp.clip(pos, 0, cap - 1)
    outs = []
    for arr, fill in zip(arrays, fills):
        buf = jnp.full((n_rows, cap), fill, dtype=arr.dtype)
        outs.append(buf.at[row, col].set(arr, mode="drop"))
    return outs, dropped


def _masked_setup(cfg, keys_block, out_keys, out_vals):
    """Per-task startup for a reduce block, added only to live output slots.

    keys_block: (N, cap); out_keys/out_vals: backend output (N, cap).
    """
    setup = jax.vmap(
        lambda k: task_setup(cfg.setup_dim, cfg.setup_rounds, k.sum())
    )(keys_block)
    live = out_keys != PAD_KEY
    return out_vals + jnp.where(live, setup[:, None], 0.0).astype(
        out_vals.dtype
    )


def reduce_phase(app, cfg, part_keys, part_vals, backend):
    """Wave-scheduled reduce: R tasks in ``reduce_waves`` waves of W workers.

    part_keys/part_vals: (R_pad, cap) with R_pad = reduce_waves * W, each row
    sorted by key with PAD_KEY padding.  The per-partition aggregation is
    delegated to ``backend`` (a :class:`~repro.mapreduce.backends.ReduceBackend`).
    Returns out_keys/out_vals of shape (R_pad, cap).
    """
    waves_r, W = cfg.reduce_waves, cfg.num_workers
    cap = part_keys.shape[1]
    pk = part_keys.reshape(waves_r, W, cap)
    pv = part_vals.reshape(waves_r, W, cap)

    def wave(carry, inp):
        k, v = inp  # (W, cap): one wave of W reduce tasks
        ok, ov = backend.reduce(k, v, app.reduce_op)
        ov = _masked_setup(cfg, k, ok, ov)
        return carry, (ok, ov)

    _, (ok, ov) = jax.lax.scan(wave, jnp.int32(0), (pk, pv))
    return ok.reshape(waves_r * W, cap), ov.reshape(waves_r * W, cap)


def reduce_local(app, cfg, part_keys, part_vals, backend):
    """Per-worker serial reduce over this worker's owned reduce slots.

    part_keys/part_vals: (slots, cap).  Each slot is one reduce task; they
    run serially (a worker processes its waves one at a time), matching the
    wave-scheduling semantics of the sharded path.
    """

    def one(carry, inp):
        k, v = inp  # (cap,)
        ok, ov = backend.reduce(k[None], v[None], app.reduce_op)
        ov = _masked_setup(cfg, k[None], ok, ov)
        return carry, (ok[0], ov[0])

    _, (ok, ov) = jax.lax.scan(one, jnp.int32(0), (part_keys, part_vals))
    return ok, ov
