"""TPU-native MapReduce engine (the paper's workload substrate).

Hadoop concepts are mapped onto JAX/XLA idioms rather than emulated:

* **map/reduce TASKS vs worker SLOTS** — M map tasks and R reduce tasks are
  scheduled over W parallel workers in ``ceil(M/W)`` / ``ceil(R/W)`` *waves*
  (wave steppers under ``fori_loop``/``jit``, ``vmap``/``shard_map`` over
  workers).  This is Hadoop's slot scheduling, and it is exactly why total
  execution time depends non-trivially and non-monotonically on (M, R) —
  the dependency the paper models.
* **per-task startup overhead** — Hadoop pays JVM/task-setup seconds per
  task; our analogue is a fixed per-task setup compute (``setup_rounds`` of a
  small matmul chain) inside each wave, plus each map task's local spill sort.
* **shuffle** — key-hash partitioning to reducers, via a pluggable
  :class:`~repro.mapreduce.backends.ShuffleBackend`: ``"lexsort"`` is a
  global sort by (reducer, key) + capacity-bounded scatter (Hadoop's fixed
  spill/partition buffers); ``"all_to_all"`` is a literal mesh collective
  used by the sharded path.
* **reduce** — per-reducer sorted segment aggregation, wave-scheduled like
  the map phase, through a pluggable
  :class:`~repro.mapreduce.backends.ReduceBackend` (``"jnp"``, ``"pallas"``,
  or ``"xla"``).

This module is deliberately thin: the shared phase primitives live in
:mod:`repro.mapreduce.phases`, the swappable strategies in
:mod:`repro.mapreduce.backends`, and the **single lowering** of the
pipeline in :mod:`repro.mapreduce.plan` — ``build_job`` /
``build_job_sharded`` only select a mode of one
:class:`~repro.mapreduce.plan.ExecutionPlan` (fused / traced / sharded;
the elastic layer's resumable mode derives from the same plan), so every
profiled path executes the same canonical wave steppers by construction.

Shapes are static per (M, R, W, L) configuration — one compile per config,
wall-clocked post-warmup, which mirrors "job execution time" in the paper
(their clusters also pay a fixed job-setup cost they do not model).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import numpy as np

from repro.mapreduce import backends as _backends
from repro.mapreduce.phases import PAD_KEY
from repro.mapreduce.plan import ExecutionPlan


@dataclasses.dataclass(frozen=True)
class JobConfig:
    """One MapReduce experiment configuration (the paper's parameter set)."""

    num_mappers: int            # M: map tasks       (paper parameter 1)
    num_reducers: int           # R: reduce tasks    (paper parameter 2)
    num_workers: int = 1        # W: parallel worker slots (cluster size)
    combiner: bool = False      # map-side combine stage between map and
    #                             shuffle (extra modeled knob): pre-aggregate
    #                             each task's pairs, contracting shuffle
    #                             bytes; requires a commutative+associative
    #                             reduce_op (COMBINABLE_OPS — the plan
    #                             rejects e.g. "first")
    capacity_factor: float = 4.0  # reducer partition capacity multiplier
    setup_rounds: int = 4       # per-task startup overhead (matmul rounds)
    setup_dim: int = 32         # startup compute size
    reduce_backend: str = "jnp"     # categorical knob: "jnp"|"pallas"|"xla"
    shuffle_backend: str = "lexsort"  # "lexsort"|"all_to_all"
    overlap_depth: int = 1          # software-pipeline depth (1 = serial)

    def __post_init__(self):
        if self.num_mappers < 1 or self.num_reducers < 1 or self.num_workers < 1:
            raise ValueError(f"bad config {self}")
        if self.overlap_depth < 1:
            raise ValueError(
                f"overlap_depth must be >= 1, got {self.overlap_depth}"
            )
        _backends.get_reduce_backend(self.reduce_backend)
        _backends.get_shuffle_backend(self.shuffle_backend)

    @property
    def map_waves(self) -> int:
        return math.ceil(self.num_mappers / self.num_workers)

    @property
    def reduce_waves(self) -> int:
        return math.ceil(self.num_reducers / self.num_workers)


@dataclasses.dataclass(frozen=True)
class MapReduceApp:
    """A MapReduce application: map emits (key, value) pairs; reduce
    aggregates values per key with ``reduce_op``.  ``sum`` and ``max`` are
    commutative+associative and therefore combiner-eligible; ``first``
    (keep the earliest value per key in delivery order) is order-dependent
    and only legal with the combiner off.

    The input is a flat int32 stream of fixed-width records of
    ``record_width`` tokens.  The plan cuts it on record boundaries into
    M splits of ``ceil(rows / M)`` records each, so the map's pair slots,
    and every capacity after them, follow from records, not tokens.
    """

    name: str
    key_space: int
    # map_fn(tokens, valid (S,)) -> keys (P,), values (P,), valid (P,)
    # with P = S * pairs_per_record: a split of S whole records with one
    # validity flag per record; tokens holds its S * record_width tokens
    # in order, flat or, for records wider than a token, as rows of
    # phases.LANES tokens where they fill whole rows
    map_fn: Callable
    record_width: int = 1  # tokens per input record
    pairs_per_record: int = 1  # pair slots the map emits per record slot
    reduce_op: str = "sum"  # "sum" | "max" | "first"

    def __post_init__(self):
        if self.record_width < 1 or self.pairs_per_record < 1:
            raise ValueError(f"bad record shape {self}")


def build_job(app: MapReduceApp, cfg: JobConfig, input_len: int,
              mesh: jax.sharding.Mesh | None = None, axis: str = "workers",
              recorder=None):
    """Compile a full MapReduce job for one (app, config, input size).

    Returns jitted ``job(tokens (input_len,) int32) ->
    (out_keys (R, C), out_vals (R, C), dropped ())``.

    ``cfg.shuffle_backend`` selects the execution strategy: a collective
    backend ("all_to_all") requires ``mesh`` and routes through
    :func:`build_job_sharded`; the default "lexsort" backend compiles the
    single-controller pipeline.

    ``recorder`` (optional) enables per-phase telemetry: any object with
    the :class:`repro.telemetry.PhaseRecorder` protocol
    (``start_job(app_name, cfg, input_len) -> trace`` where the trace has
    ``record_phase(name, wall_s, **counters)`` / ``finish(total_s)``).
    With a recorder the phases compile separately (fenced and
    wall-clocked — on the sharded path too, as separate mesh programs)
    and each call of the returned job appends one trace; with
    ``recorder=None`` (default) the fused single-program mode compiles —
    telemetry off costs nothing.
    """
    shuffle = _backends.get_shuffle_backend(cfg.shuffle_backend)
    if shuffle.collective:
        if mesh is None:
            raise ValueError(
                f"shuffle backend {shuffle.name!r} is a mesh collective; "
                "pass mesh= (or call build_job_sharded)"
            )
        return build_job_sharded(
            app, cfg, input_len, mesh, axis, recorder=recorder
        )
    if mesh is not None:
        raise ValueError(
            f"mesh given but shuffle backend {shuffle.name!r} is "
            "single-controller; use shuffle_backend=\"all_to_all\" for a "
            "distributed job"
        )
    plan = ExecutionPlan(app, cfg, input_len)
    if recorder is not None:
        return plan.traced(recorder)
    if cfg.overlap_depth > 1:
        return plan.pipelined()
    return plan.fused()


def build_job_sharded(
    app: MapReduceApp, cfg: JobConfig, input_len: int, mesh: jax.sharding.Mesh,
    axis: str = "workers", counters: bool = False, recorder=None,
):
    """shard_map MapReduce: W = mesh axis size; shuffle = all_to_all.

    A thin wrapper over :meth:`ExecutionPlan.sharded` — ``shard_map``
    over the plan's own map and reduce waves, each worker running its own
    task rows and partitions through them one at a time.  This is the
    deployment path for real multi-chip meshes; semantics match
    :func:`build_job`.

    With ``counters=True`` the returned job yields ``(out_keys, out_vals,
    dropped, stats)`` where ``stats`` reduces the per-worker overflow
    counters across shards into true per-phase totals::

        stats = {
            "dropped_send": int,   # shuffle send-buffer overflow, all workers
            "dropped_recv": int,   # reduce-bucket overflow, all workers
            "dropped_per_worker": (W, 2) ndarray,  # [send, recv] per worker
        }

    With ``recorder=`` the phases compile as separate mesh programs and
    every call appends a per-phase :class:`~repro.telemetry.JobTrace` —
    per-phase *wall times* on the sharded path, recorded by the same
    fenced runner as the traced mode's.
    """
    plan = ExecutionPlan(app, cfg, input_len)
    return plan.sharded(mesh, axis, counters=counters, recorder=recorder)


def collect_results(out_keys, out_vals) -> dict[int, int]:
    """Gather (key -> aggregated value) from job output, host-side."""
    out_keys = np.asarray(out_keys).ravel()
    out_vals = np.asarray(out_vals).ravel()
    mask = out_keys != int(PAD_KEY)
    result: dict[int, int] = {}
    for k, v in zip(out_keys[mask], out_vals[mask]):
        result[int(k)] = result.get(int(k), 0) + int(v)
    return result
