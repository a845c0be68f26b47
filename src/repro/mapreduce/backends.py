"""Pluggable shuffle/reduce backends for the MapReduce phase pipeline.

The paper models total execution time as a function of configuration
parameters (M, R, ...).  This module turns the *execution strategy* itself
into one more configuration axis: a ``JobConfig`` names a reduce backend and
a shuffle backend by string, the engine resolves them here, and the tuner
can treat the backend as a categorical knob (one model per category — the
paper's per-application model-database pattern, reused per-backend).

Reduce backends (per-partition sorted segment aggregation, all implementing
the same contract as :func:`repro.mapreduce.phases.segment_sum_sorted`):

* ``jnp``    — a reverse segmented scan over each sorted row (the
  portable reference; no gather, scatter or sort);
* ``pallas`` — the Pallas TPU ``segment_reduce`` kernel (MXU one-hot
  matmul formulation; interpret mode on the CPU platform only), ``sum``
  only, partition width at most ``kernels.segment_reduce.MAX_C``;
* ``xla``    — the same scan as ``jnp`` under the name that existing job
  configurations select.

Shuffle backends:

* ``lexsort``    — single-controller global sort by (reducer, key) +
  capacity-bounded scatter;
* ``all_to_all`` — per-worker partition + a literal mesh ``all_to_all``
  (the multi-chip deployment path; used inside ``shard_map``).

Registering a new backend is one call::

    register_reduce_backend(MyBackend())
    JobConfig(..., reduce_backend="mine")   # now valid
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.mapreduce import phases
from repro.mapreduce.phases import PAD_KEY, bucket_scatter, hash_to_reducer


# ---------------------------------------------------------------------------
# Reduce backends
# ---------------------------------------------------------------------------


class ReduceBackend:
    """Per-partition sorted segment aggregation.

    ``reduce(keys, values, reduce_op)`` takes (N, C) blocks — N partitions,
    each row sorted by key with PAD_KEY padding — and returns (out_keys,
    out_vals) of the same shape: the aggregate of each equal-key run at its
    first occurrence, (PAD_KEY, 0) elsewhere.

    ``combine(keys, values, reduce_op)`` is the map-side variant of the
    same aggregation: identical validity contract, but each row's
    aggregates come back *front-packed* in ascending key order with a
    (PAD_KEY, 0) tail — so the caller can truncate the row to its
    distinct-key bound and shrink the shuffle stream.  The default
    derivation sorts the sparse ``reduce`` output with its values
    (first occurrences of a sorted row are ascending and distinct, so an
    ascending key sort IS the compaction); backends with a native
    compacting kernel override it.
    """

    name: str = "abstract"
    supported_ops: tuple[str, ...] = ()

    def reduce(self, keys, values, reduce_op: str):
        raise NotImplementedError

    def combine(self, keys, values, reduce_op: str):
        ok, ov = self.reduce(keys, values, reduce_op)
        # One sort carrying the values: a row's first-occurrence keys are
        # distinct and every other slot is (PAD_KEY, 0), which sorts last,
        # so the order of equal keys cannot matter.
        return tuple(jax.lax.sort((ok, ov), dimension=1, num_keys=1))


class JnpReduceBackend(ReduceBackend):
    """Portable reference: a reverse segmented scan per row (pure jnp,
    :func:`repro.mapreduce.phases.segment_sum_sorted`)."""

    name = "jnp"
    supported_ops = ("sum", "max", "first")

    def reduce(self, keys, values, reduce_op: str):
        ok, ov, _ = phases.segment_sum_sorted(
            keys, values, keys != PAD_KEY, reduce_op
        )
        return ok, ov


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run interpreted: only on the CPU
    platform (tests, debugging).  A TPU compiles them; any other platform
    raises rather than silently running the interpreter in place of the
    kernel a run meant to measure."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise ValueError(
        f"pallas reduce backend runs compiled on 'tpu' or interpreted on "
        f"'cpu', not on {platform!r}"
    )


class PallasReduceBackend(ReduceBackend):
    """The Pallas TPU segment-reduce kernel (one grid step per partition).

    Accumulates on the MXU in float32, so integer aggregates are exact only
    while every partial sum stays below ``EXACT_INT_BOUND`` (2**24); beyond
    that the result silently loses low bits relative to the jnp/xla
    backends.  Workloads with per-key totals near that bound should use a
    different backend (tests/test_backends.py pins this boundary).

    The kernels hold a (C, C) one-hot in VMEM, so a partition (or, with
    the combiner, a map-task row) wider than ``MAX_C`` raises a
    ``ValueError`` naming the limit when the job is traced.
    """

    name = "pallas"
    supported_ops = ("sum",)
    EXACT_INT_BOUND = 2 ** 24  # float32 integer-exactness limit

    def _check_op(self, reduce_op: str):
        if reduce_op not in self.supported_ops:
            raise ValueError(
                f"pallas reduce backend supports {self.supported_ops}, "
                f"got {reduce_op!r}"
            )

    def reduce(self, keys, values, reduce_op: str):
        self._check_op(reduce_op)
        from repro.kernels.segment_reduce import segment_reduce

        return segment_reduce(keys, values, interpret=pallas_interpret())

    def combine(self, keys, values, reduce_op: str):
        # Native compacting kernel: the one-hot segment matmul indexed by
        # segment id front-packs in one pass — no host-visible sort.
        self._check_op(reduce_op)
        from repro.kernels.local_reduce import local_reduce

        return local_reduce(keys, values, interpret=pallas_interpret())


class XlaReduceBackend(JnpReduceBackend):
    """The ``jnp`` backend's segmented scan under a second registered name,
    which existing job configurations and traffic files select."""

    name = "xla"


# ---------------------------------------------------------------------------
# Shuffle backends
# ---------------------------------------------------------------------------


class ShuffleBackend:
    """Routes map-output pairs into per-reduce-task partitions.

    Two structural families share this interface:

    * non-collective (``collective = False``): :meth:`partition` sees the
      job's full flat pair stream and returns global (R_pad, cap)
      partitions — used by the single-controller path;
    * collective (``collective = True``): :meth:`exchange` runs *inside* a
      ``shard_map`` worker body on that worker's local pairs and returns the
      (slots, cap) reduce buckets the worker owns after the exchange.

    Both return a ``dropped`` count for capacity-overflow accounting.
    """

    name: str = "abstract"
    collective: bool = False

    def partition(self, cfg, keys, values, pvalid):
        raise NotImplementedError(f"{self.name} is not a global shuffle")

    def exchange(self, cfg, axis, keys, values, pvalid):
        raise NotImplementedError(f"{self.name} is not a collective shuffle")

    def capacity_for(self, cfg, n_pairs: int) -> int:
        """Per-partition slot capacity this backend will allocate for a job
        with ``n_pairs`` total map-output pairs.  The telemetry layer reads
        this to size its counters; must match what :meth:`partition` /
        :meth:`exchange` actually use."""
        return phases.partition_capacity(
            n_pairs, cfg.num_reducers, cfg.capacity_factor
        )


class LexsortShuffle(ShuffleBackend):
    """Single-controller shuffle: global sort by (reducer, key) + scatter."""

    name = "lexsort"
    collective = False

    def partition(self, cfg, keys, values, pvalid):
        """keys/values/pvalid: flat (n,).  Returns (part_keys, part_vals,
        dropped) with partitions of shape (reduce_waves * W, cap)."""
        R, W = cfg.num_reducers, cfg.num_workers
        n = keys.shape[0]
        rid = hash_to_reducer(keys, R)
        rid = jnp.where(pvalid, rid, R)  # invalid pairs -> OOB dump row
        # Global shuffle sort: primary reducer id, secondary key.
        order = jnp.lexsort((keys, rid))
        skeys, svals, srid = keys[order], values[order], rid[order]
        cap = phases.partition_capacity(n, R, cfg.capacity_factor)
        R_pad = cfg.reduce_waves * W
        (part_keys, part_vals), dropped = bucket_scatter(
            srid, R, R_pad, cap, (skeys, svals), (PAD_KEY, 0)
        )
        return part_keys, part_vals, dropped


class AllToAllShuffle(ShuffleBackend):
    """Mesh shuffle: per-worker partition by destination + ``all_to_all``.

    Runs inside a ``shard_map`` worker body.  Reducer r lives on worker
    r % W; after the exchange each worker buckets its received pairs into
    the ``reduce_waves`` local reduce slots it owns (local slot = r // W).

    The worker-local halves are exposed as :meth:`pack` (before the
    collective) and :meth:`unpack` (after it) so non-mesh callers can
    compose them around an equivalent data movement: the elastic
    resumable path (``repro.elastic.resumable``) vmaps both halves over a
    worker axis and replaces the literal ``all_to_all`` with the block
    transpose it implements — one implementation, two execution modes.
    """

    name = "all_to_all"
    collective = True

    def pack(self, cfg, keys, values, pvalid):
        """Worker-local pre-exchange half: partition this worker's flat
        (n_local,) pairs by destination worker.  Returns ((send_k, send_v,
        send_r), dropped) with (W, shuf_cap) send buffers — row i goes to
        worker i — and the count lost to send-buffer overflow."""
        R, W = cfg.num_reducers, cfg.num_workers
        n_local = keys.shape[0]
        # Per (src, dst) shuffle capacity: uniform share x safety factor.
        shuf_cap = phases.partition_capacity(n_local, W, cfg.capacity_factor)
        # Partition local pairs by destination worker = rid % W.
        rid = jnp.where(pvalid, hash_to_reducer(keys, R), R)
        dst = jnp.where(pvalid, rid % W, W)
        order = jnp.lexsort((keys, rid, dst))
        k, v, rid, dst = (
            keys[order], values[order], rid[order], dst[order]
        )
        (send_k, send_v, send_r), send_dropped = bucket_scatter(
            dst, W, W, shuf_cap, (k, v, rid), (PAD_KEY, 0, R)
        )
        return (send_k, send_v, send_r), send_dropped

    def unpack(self, cfg, n_local, rk, rv, rr):
        """Worker-local post-exchange half: bucket the received flat pairs
        into this worker's reduce tasks (local slot = rid // W, since
        reducer r lives on worker r % W).  ``n_local`` is the per-worker
        map-output pair count, which sizes the reduce-bucket capacity the
        same way on every worker.  Returns ((bk, bv), dropped) with
        buckets of shape (reduce_waves, red_cap)."""
        R, W, waves_r = cfg.num_reducers, cfg.num_workers, cfg.reduce_waves
        red_cap = phases.partition_capacity(
            W * n_local, R, cfg.capacity_factor
        )
        lslot = jnp.where(rr < R, rr // W, waves_r)
        order = jnp.lexsort((rk, lslot))
        rk, rv, lslot = rk[order], rv[order], lslot[order]
        (bk, bv), recv_dropped = bucket_scatter(
            lslot, waves_r, waves_r, red_cap, (rk, rv), (PAD_KEY, 0)
        )
        return (bk, bv), recv_dropped

    def exchange(self, cfg, axis, keys, values, pvalid):
        """keys/values/pvalid: this worker's flat (n_local,) pairs.
        Returns (bucket_keys, bucket_vals, dropped) with buckets of shape
        (reduce_waves, red_cap) and ``dropped`` a per-phase (2,) vector
        ``[send_dropped, recv_dropped]`` — send-buffer overflow vs
        reduce-bucket overflow, kept separate so the sharded path can
        report true per-phase counters, not just the aggregate."""
        n_local = keys.shape[0]
        (send_k, send_v, send_r), send_dropped = self.pack(
            cfg, keys, values, pvalid
        )
        # The shuffle: exchange partition i with worker i (tiled all_to_all:
        # row i of the (W, cap) send buffer goes to worker i, received rows
        # re-stack along the same axis).
        recv_k = jax.lax.all_to_all(send_k, axis, 0, 0, tiled=True)
        recv_v = jax.lax.all_to_all(send_v, axis, 0, 0, tiled=True)
        recv_r = jax.lax.all_to_all(send_r, axis, 0, 0, tiled=True)
        (bk, bv), recv_dropped = self.unpack(
            cfg, n_local,
            recv_k.reshape(-1), recv_v.reshape(-1), recv_r.reshape(-1),
        )
        return bk, bv, jnp.stack([send_dropped, recv_dropped])


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

REDUCE_BACKENDS: dict[str, ReduceBackend] = {}
SHUFFLE_BACKENDS: dict[str, ShuffleBackend] = {}


def register_reduce_backend(backend: ReduceBackend) -> ReduceBackend:
    if not backend.supported_ops:
        raise ValueError(f"backend {backend.name!r} supports no reduce ops")
    REDUCE_BACKENDS[backend.name] = backend
    return backend


def register_shuffle_backend(backend: ShuffleBackend) -> ShuffleBackend:
    SHUFFLE_BACKENDS[backend.name] = backend
    return backend


register_reduce_backend(JnpReduceBackend())
register_reduce_backend(PallasReduceBackend())
register_reduce_backend(XlaReduceBackend())
register_shuffle_backend(LexsortShuffle())
register_shuffle_backend(AllToAllShuffle())


def get_reduce_backend(name: str) -> ReduceBackend:
    try:
        return REDUCE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown reduce backend {name!r}; "
            f"registered: {sorted(REDUCE_BACKENDS)}"
        ) from None


def get_shuffle_backend(name: str) -> ShuffleBackend:
    try:
        return SHUFFLE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown shuffle backend {name!r}; "
            f"registered: {sorted(SHUFFLE_BACKENDS)}"
        ) from None
