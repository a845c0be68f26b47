"""One wave-stepper execution core: every execution mode is a derivation.

:class:`ExecutionPlan` lowers one ``(MapReduceApp, JobConfig,
input_len)`` into a single canonical stepper set over **task-major
buffers**, and every entry point is a *mode* over that one plan.  Every
mode maps through the same map wave and reduces through the same reduce
wave (a compute/commit pair sliding a window of task rows), so no mode
is a second lowering whose time the paper's models would mis-attribute:

* :meth:`fused`     — ``fori_loop`` over the steppers under one ``jit``:
  the zero-overhead hot path (``build_job``'s default);
* :meth:`traced`    — the same stepper loops jitted per phase, fenced and
  wall-clocked, feeding a :class:`repro.telemetry.PhaseRecorder`;
* :meth:`pipelined` — the fused pipeline with map/reduce waves
  software-pipelined at ``cfg.overlap_depth``: wave group g's compute
  overlaps group g-1's commit in one loop carry (prologue / steady
  state / epilogue), bit-exact vs fused by construction;
* :meth:`sharded`   — ``shard_map`` over the same waves (workers = mesh
  axis, each running its own task rows and partitions one at a time;
  shuffle = literal ``all_to_all``); with a recorder the phases compile
  as *separate* mesh programs, run and recorded by the same fenced
  runner as :meth:`traced`;
* :meth:`resumable` — the raw steppers jitted per grant for
  :class:`repro.elastic.resumable.ResumableJob`'s wave-boundary
  stop/snapshot/regrant/resume loop.

The canonical stepper contract (all shapes static per plan):

* ``prep(tokens)``                        → ``(splits (M, S·w), valid (M, S))``:
  the record-aligned split, ``S = ceil(rows / M)`` whole records of
  ``w = app.record_width`` tokens per task, one validity flag per record;
* ``map_step(W)(splits, valid, bk, bv, bp, start)``
                                          → updated ``(M, P)`` accumulators:
  commit of compute (:meth:`ExecutionPlan._map_wave`) at ``start``;
* ``combine_step()(bk, bv, bp)``          → compacted ``(M, Pc)`` task rows
  (only when ``cfg.combiner``): per-task local segment-reduce +
  front-packing through the reduce backend's ``combine``, with
  ``Pc = min(P, key_space)`` — the static distinct-key bound — so every
  downstream capacity shrinks with the combined stream;
* ``shuffle_step(W)(bk, bv, bp)``         → ``(pk, pv, dropped, ok0, ov0)``
  with partitions ``(R, cap)``; the ``lexsort`` backend uses the
  *canonical* W-independent capacity ``partition_capacity(M·P, R, f)``,
  the ``all_to_all`` backend the capacity layout of a real W-device run
  (its pack/unpack halves vmapped over a worker axis, the collective
  replaced by the block transpose it implements);
* ``reduce_step(W)(pk, pv, ok, ov, start)`` → updated ``(R, cap)`` outputs,
  likewise of :meth:`ExecutionPlan._reduce_wave`.

Every phase function runs under the named scope ``mr.<phase>``
(:func:`repro.mapreduce.phases.scoped`), in every mode, so a profile of
any of these programs can attribute each device op to its phase; and
every mode can count, on the device, the pairs and slots at each phase
boundary (``fused(counters=True)``, ``pipelined(counters=True)``, and the
counters ``traced`` and ``sharded(recorder=)`` record).

A map task's output depends only on its split and the frozen config —
never on W or on which wave (or mode) ran it — and all buffers are
task-major with exactly M (or R) live rows, so bit-exactness across
modes is a property of construction, checked once by the equivalence
suite in ``tests/test_plan.py`` instead of once per hand-rolled path.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os as _os
import time as _time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.mapreduce import backends as _backends
from repro.mapreduce import phases
from repro.mapreduce.phases import PAD_KEY, run_map_task

__all__ = ["ExecutionPlan"]


# Parallelism ceiling recorded with every process-CPU-clock sample: the
# runtime (XLA) is free to use every host core inside one fenced phase,
# so the trace's CPU conservation law is cpu_s <= wall_s * cpu_workers.
_NCPU = float(_os.cpu_count() or 1)


#: fill of each buffer a map wave writes (keys, values, pair validity) and
#: of each a reduce wave writes (keys, values)
_MAP_FILLS = (PAD_KEY, 0, False)
_REDUCE_FILLS = (PAD_KEY, 0)


def _pad_rows(arr, n_extra: int, fill):
    """Append ``n_extra`` fill-rows so dynamic W-row windows never clamp."""
    if n_extra == 0:
        return arr
    pad = jnp.full((n_extra,) + arr.shape[1:], fill, dtype=arr.dtype)
    return jnp.concatenate([arr, pad], axis=0)


# A wave's window: W task rows from ``start`` of arrays of ``rows`` rows.
# Padding is only needed when the grant exceeds the task count (slice size
# must fit the array).  For a final *partial* wave, XLA clamps the dynamic
# start so the W-row window shifts onto already-processed rows — which
# recompute bit-identically (map tasks are deterministic, reduce backends
# row-independent by contract), so the in-place window needs no per-wave
# pad/copy of the carried buffers.


def _take(arrays, fills, start, W: int, rows: int):
    """The window's rows of each of ``arrays``."""
    pad = max(0, W - rows)
    return tuple(
        jax.lax.dynamic_slice_in_dim(_pad_rows(a, pad, f), start, W, 0)
        for a, f in zip(arrays, fills)
    )


def _put(bufs, blocks, fills, start, W: int, rows: int):
    """``bufs`` with the window's rows replaced by ``blocks``."""
    pad = max(0, W - rows)
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(
            _pad_rows(buf, pad, f), blk, start, 0
        )[:rows]
        for buf, blk, f in zip(bufs, blocks, fills)
    )


def _buffers(fills, rows: int, width: int):
    """Fresh ``(rows, width)`` buffers, one per fill (int32 keys and
    values, bool validity)."""
    return tuple(
        jnp.full((rows, width), f, bool if f is False else jnp.int32)
        for f in fills
    )


def _step(compute, commit):
    """The serial wave step of a compute/commit pair: commit of compute at
    the same start, ``step(x, y, *bufs, start) -> bufs``."""

    def step(x, y, *bufs_start):
        *bufs, start = bufs_start
        return commit(tuple(bufs), compute(x, y, start), start)

    return step


def _reducer_major(x, R: int):
    """``(W, waves_r, cap)`` worker-major reduce slots -> ``(R, cap)``
    indexed by reducer id: reducer r lives on worker r % W at local slot
    r // W, so row r of the slot-major stacking is reducer r's."""
    return x.transpose(1, 0, 2).reshape(-1, x.shape[-1])[:R]


def _phase_counts(records) -> dict:
    """The device counters of each phase, from its program's arguments and
    outputs (the functions of :mod:`repro.mapreduce.phases`).
    ``records(*map_args)`` is the record validity of what the map reads;
    the shuffle's counters also count the partitions it hands the
    reduce."""
    return {
        "map": lambda args, out: phases.map_counts(records(*args), out[2]),
        "combine": lambda args, out: phases.pair_counts("combine", out[2]),
        "shuffle": lambda args, out: {
            **phases.shuffle_counts(args[0], args[2], out[2]),
            **phases.partition_counts(out[0]),
        },
        "reduce": lambda args, out: phases.output_counts(out[0]),
    }


def _compose(fns: dict, counts: dict | None = None):
    """The job of a phase dict (:meth:`ExecutionPlan.phase_fns` and kin):
    map, the combine if present, shuffle, reduce.  ``job(*inputs) -> (ok,
    ov, dropped)``; given ``counts`` (:func:`_phase_counts`) also a dict of
    the phases' int32 device counters, computed in the same program."""

    def job(*inputs):
        stats = {}

        def run(phase, *args):
            out = fns[phase](*args)
            if counts is not None:
                stats.update(counts[phase](args, out))
            return out

        bufs = run("map", *inputs)
        if "combine" in fns:
            bufs = run("combine", *bufs)
        pk, pv, dropped = run("shuffle", *bufs)
        ok, ov = run("reduce", pk, pv)
        if counts is not None:
            return ok, ov, dropped, stats
        return ok, ov, dropped

    return job


def _counted(count, fn):
    """``fn`` jitted as one fenced phase program that also returns the
    phase's device counters (``count``, one entry of
    :func:`_phase_counts`): ``prog(*args) -> (out, counts)``."""

    @functools.wraps(fn)
    def prog(*args):
        out = fn(*args)
        return out, count(args, out)

    return jax.jit(prog)


def _fenced(phase: str, prog, counts: dict, *args):
    """Run one :func:`_counted` program to completion inside the host span
    ``mr.<phase>`` (on the profiler's clock, beside the device ops of the
    same scope).  Adds its counters to ``counts`` as ints and returns
    ``(out, wall_s, cpu_s)``."""
    with TraceAnnotation(f"{phases.SCOPE}.{phase}"):
        t0 = _time.perf_counter()
        c0 = _time.process_time()
        out, c = jax.block_until_ready(prog(*args))
        cpu = _time.process_time() - c0
        dt = _time.perf_counter() - t0
    counts.update((k, int(v)) for k, v in jax.device_get(c).items())
    return out, dt, cpu


class ExecutionPlan:
    """One (app, config, input size), lowered once; modes derive from it.

    ``cfg.num_workers`` is the *default* grant (the one :meth:`fused`,
    :meth:`traced`, and :meth:`meta` use); steppers are built per grant on
    demand and cached, which is what lets the resumable mode re-plan the
    remaining waves under a different W mid-flight.
    """

    def __init__(self, app, cfg, input_len: int):
        self.app = app
        self.cfg = cfg
        self.input_len = int(input_len)
        self.reduce_backend = _backends.get_reduce_backend(cfg.reduce_backend)
        if app.reduce_op not in self.reduce_backend.supported_ops:
            raise ValueError(
                f"reduce backend {self.reduce_backend.name!r} supports "
                f"{self.reduce_backend.supported_ops}, but app "
                f"{app.name!r} needs {app.reduce_op!r}"
            )
        self.shuffle = _backends.get_shuffle_backend(cfg.shuffle_backend)
        self.combiner = bool(getattr(cfg, "combiner", False))
        if self.combiner and app.reduce_op not in phases.COMBINABLE_OPS:
            raise ValueError(
                f"combiner requires a commutative+associative reduce op "
                f"{phases.COMBINABLE_OPS}, but app {app.name!r} uses "
                f"{app.reduce_op!r}"
            )
        self.M = cfg.num_mappers
        self.R = cfg.num_reducers
        if self.input_len % app.record_width:
            raise ValueError(
                f"input of {self.input_len} tokens is not whole records of "
                f"{app.record_width} tokens"
            )
        #: input records, and the records of each map task's split
        self.rows = self.input_len // app.record_width
        self.S = math.ceil(self.rows / self.M)
        #: pair slots each map task emits
        self.P = self.S * app.pairs_per_record
        #: combined per-task row width (static distinct-key bound)
        self.combine_cap = phases.combine_capacity(self.P, app.key_space)
        #: column width of the task rows entering the shuffle barrier
        self.shuffle_width = self.combine_cap if self.combiner else self.P
        #: canonical (W-independent) lexsort partition capacity — sized
        #: from the *combined* stream when the combiner is on, so the
        #: byte contraction propagates into the partition buffers too
        self.lex_capacity = phases.partition_capacity(
            self.M * self.shuffle_width, self.R, cfg.capacity_factor
        )
        # Per-grant jitted stepper caches (shared by every mode and every
        # ResumableJob derived from this plan).  Keys are canonicalized:
        # any grant W >= M (or R) compiles the same stepper as W == M, so
        # re-planning after a regrant to an equivalent grant is a cache
        # hit, not a re-trace.  Every key carries the combiner flag —
        # combined and uncombined grants must never share a jitted trace
        # (their buffer widths differ).
        self._jit_prep = None
        self._jit_map: dict[tuple[int, bool], callable] = {}
        self._jit_combine = None
        self._jit_shuffle: dict[tuple[int, bool], callable] = {}
        self._jit_reduce: dict[tuple[int, int, bool], callable] = {}
        self._jit_pipelined: dict[tuple[int, int, bool], callable] = {}
        self._cache_hits = 0
        self._cache_misses = 0
        # The map of every mode but the fused mesh program reads the
        # whole input, split as :meth:`_split` splits it.
        self._counts = _phase_counts(lambda tokens: self._valid(self.M))

    # ------------------------------------------------------------- metadata

    def partition_cap(self, workers: int | None = None) -> int:
        """Partition capacity the shuffle barrier will allocate at a grant
        (lexsort: canonical, W-free; all_to_all: the W-shaped layout)."""
        if not self.shuffle.collective:
            return self.lex_capacity
        W = self.cfg.num_workers if workers is None else int(workers)
        cfg_w = dataclasses.replace(self.cfg, num_workers=W)
        n_local = cfg_w.map_waves * self.shuffle_width
        return phases.partition_capacity(
            W * n_local, self.R, self.cfg.capacity_factor
        )

    def meta(self, workers: int | None = None) -> dict:
        """Static shape facts telemetry and the cost estimator need."""
        W = self.cfg.num_workers if workers is None else int(workers)
        return {
            "input_len": self.input_len,
            "record_width": self.app.record_width,
            "records": self.rows,
            "mappers": self.M,
            "reducers": self.R,
            "workers": W,
            "split_size": self.S,
            "map_waves": math.ceil(self.M / W),
            "reduce_waves": math.ceil(self.R / W),
            "n_pairs": self.M * self.P,
            "combiner": self.combiner,
            "combine_capacity": self.combine_cap,
            "shuffle_width": self.shuffle_width,
            "partition_capacity": self.partition_cap(W),
            "r_pad": self.R,
            "overlap_depth": getattr(self.cfg, "overlap_depth", 1),
        }

    # ------------------------------------------------- raw stepper builders

    def _valid(self, tasks: int):
        """``(tasks, S)``: which record slots of ``tasks`` splits hold a
        record of the input."""
        return (jnp.arange(tasks * self.S) < self.rows).reshape(
            tasks, self.S
        )

    def _split(self, tokens, tasks: int):
        """The one record-aligned input split every mode maps: ``tasks``
        splits of ``S`` whole records each, ``(tasks, S * w)`` tokens, and
        their record validity ``(tasks, S)``.  Record slots past the
        input's last record are zero and invalid.

        A split of records wider than a token whose tokens fill whole
        vector registers is laid out as ``(tasks, S * w / LANES, LANES)``:
        the flat input's own tiling, so the split is a view of the input.
        A 2-D split would be tiled by 8 tasks on the TPU, a copy of the
        whole input padded to a multiple of 8 tasks."""
        width = self.app.record_width
        padded = jnp.zeros((tasks * self.S * width,), jnp.int32).at[
            :self.input_len
        ].set(tokens)
        valid = self._valid(tasks)
        shape = (tasks, self.S * width)
        if width > 1 and self.S * width % phases.LANES == 0:
            shape = (tasks, self.S * width // phases.LANES, phases.LANES)
        return padded.reshape(shape), valid

    def _prep_fn(self):
        M, input_len = self.M, self.input_len

        def prep(tokens):
            if tokens.shape != (input_len,):
                raise ValueError(
                    f"expected ({input_len},), got {tokens.shape}"
                )
            return self._split(tokens, M)

        return prep

    def initial_map_buffers(self):
        return _buffers(_MAP_FILLS, self.M, self.P)

    def initial_reduce_buffers(self, cap: int):
        return _buffers(_REDUCE_FILLS, self.R, cap)

    # -------------------------------------------- the map and reduce waves
    #
    # Each wave is split at its data-dependency boundary: ``compute`` reads
    # only the immutable inputs (splits / partitions) and produces a block
    # of W task rows; ``commit`` writes the block into the carried
    # buffers.  The serial step is commit of compute at the same start
    # (:func:`_step`); the pipelined mode overlaps group g's compute with
    # group g-1's commit.  Each wave slides over ``rows`` task rows: M
    # (or R) for the plan's buffers, a worker's own waves_m (or waves_r)
    # rows on a mesh — so every mode maps and reduces through the same
    # wave code.

    def _map_wave(self, W: int, rows: int):
        app, cfg = self.app, self.cfg

        def compute(splits, svalid, start):
            tok, val = _take((splits, svalid), (0, False), start, W, rows)
            return jax.vmap(
                lambda t, m: run_map_task(app, cfg, t, m)
            )(tok, val)

        def commit(bufs, blk, start):
            return _put(bufs, blk, _MAP_FILLS, start, W, rows)

        return compute, commit

    def _reduce_wave(self, W: int, rows: int):
        op, cfg = self.app.reduce_op, self.cfg
        backend = self.reduce_backend

        def compute(pk, pv, start):
            kblk, vblk = _take((pk, pv), _REDUCE_FILLS, start, W, rows)
            ok, ov = backend.reduce(kblk, vblk, op)
            return ok, phases._masked_setup(cfg, kblk, ok, ov)

        def commit(bufs, blk, start):
            return _put(bufs, blk, _REDUCE_FILLS, start, W, rows)

        return compute, commit

    def _combine_step_fn(self):
        """Map-side combine barrier: aggregate + compact every task row.

        W-independent like the lexsort barrier — combining is per-row, so
        one batched backend call covers all M tasks regardless of the
        grant held when the barrier executes (bit-exact under regrants by
        construction).
        """
        backend, op = self.reduce_backend, self.app.reduce_op
        cap = self.combine_cap

        def step(bk, bv, bp):
            return phases.combine_rows(backend, bk, bv, bp, op, cap)

        return step

    def _shuffle_step_fn(self, W: int):
        if self.shuffle.collective:
            return self._a2a_shuffle_fn(W)
        return self._lexsort_shuffle_fn()

    def _lexsort_shuffle_fn(self):
        """Canonical single-controller shuffle: W-independent capacity.

        Reuses :meth:`LexsortShuffle.partition` with a W=1 view of the
        config so its ``reduce_waves * W`` row padding degenerates to
        exactly R rows — the canonical partition block.
        """
        cfg_w1 = dataclasses.replace(self.cfg, num_workers=1)
        shuffle, R = self.shuffle, self.R
        init_out = self.initial_reduce_buffers

        def step(bk, bv, bp):
            n = bk.shape[0] * bk.shape[1]
            pk, pv, dropped = shuffle.partition(
                cfg_w1, bk.reshape(n), bv.reshape(n), bp.reshape(n)
            )
            ok, ov = init_out(pk.shape[1])
            return pk, pv, dropped, ok, ov

        return step

    def _a2a_shuffle_fn(self, W: int):
        """The collective shuffle, single-controller: vmap pack/unpack
        over a worker axis, block-transpose in place of ``all_to_all``.

        Reproduces the per-worker computation (and capacity layout) of a
        real W-device :meth:`sharded` run at the grant held when the
        barrier executes.
        """
        cfg_w = dataclasses.replace(self.cfg, num_workers=W)
        shuffle, M, R = self.shuffle, self.M, self.R
        waves_m = cfg_w.map_waves
        M_pad = waves_m * W
        init_out = self.initial_reduce_buffers

        def step(bk, bv, bp):
            # Column width comes from the input, not the config: the
            # combiner hands this barrier compacted (M, Pc) rows, and the
            # per-worker stream (hence the exchange capacity) shrinks
            # with them — same contraction a real mesh run sees.
            Pb = bk.shape[1]
            n_local = waves_m * Pb

            # Worker-major local streams: worker w owns tasks w, w+W, ...
            def per_worker(buf, fill):
                padded = _pad_rows(buf, M_pad - M, fill)
                return padded.reshape(waves_m, W, Pb).transpose(
                    1, 0, 2
                ).reshape(W, n_local)

            k2 = per_worker(bk, PAD_KEY)
            v2 = per_worker(bv, 0)
            p2 = per_worker(bp, False)
            (send_k, send_v, send_r), sdrop = jax.vmap(
                lambda k, v, p: shuffle.pack(cfg_w, k, v, p)
            )(k2, v2, p2)
            # all_to_all(tiled): worker w's received row j is worker j's
            # send row w — a block transpose of the (W, W, cap) tensor.
            recv_k = send_k.transpose(1, 0, 2)
            recv_v = send_v.transpose(1, 0, 2)
            recv_r = send_r.transpose(1, 0, 2)
            (bk2, bv2), rdrop = jax.vmap(
                lambda k, v, r: shuffle.unpack(
                    cfg_w, n_local,
                    k.reshape(-1), v.reshape(-1), r.reshape(-1),
                )
            )(recv_k, recv_v, recv_r)
            pk, pv = _reducer_major(bk2, R), _reducer_major(bv2, R)
            ok, ov = init_out(pk.shape[1])
            return pk, pv, sdrop.sum() + rdrop.sum(), ok, ov

        return step

    @staticmethod
    def _serial_loop(compute, commit, waves: int, stride: int, init_bufs):
        """``waves`` serial wave steps from row 0, ``stride`` rows apart,
        under one ``fori_loop``: ``run(x, y) -> bufs`` from
        ``init_bufs(x, y)``."""
        step = _step(compute, commit)

        def run(x, y):
            def body(i, bufs):
                return step(x, y, *bufs, i * stride)

            return jax.lax.fori_loop(0, waves, body, init_bufs(x, y))

        return run

    @staticmethod
    def _software_pipeline(compute, commit, groups: int, stride: int,
                           init_bufs):
        """Prologue / steady-state / epilogue over ``groups`` wave groups.

        Iteration g of the steady-state ``fori_loop`` commits group g-1's
        block *and* computes group g's — the two halves touch disjoint
        state, so XLA's thunk scheduler may overlap them.  The commit
        order (0, 1, ..., G-1) and every slice/clamp is identical to the
        serial loop, so outputs are bit-exact by construction.
        """

        def run(x, y):
            blk = compute(x, y, 0)

            def body(g, carry):
                bufs, blk = carry
                bufs = commit(bufs, blk, (g - 1) * stride)
                return bufs, compute(x, y, g * stride)

            bufs, blk = jax.lax.fori_loop(
                1, groups, body, (init_bufs(x, y), blk)
            )
            return commit(bufs, blk, (groups - 1) * stride)

        return run

    def pipelined_phase_fns(self, workers: int | None = None,
                            depth: int | None = None) -> dict:
        """The pipeline as pure phase functions (map, the combine when
        ``cfg.combiner``, shuffle, reduce), each under its named scope
        ``mr.<phase>``, with map and reduce waves grouped D at a time into
        blocks of ``W*D`` tasks (overlap depth D, default
        ``cfg.overlap_depth``).  At ``D = 1`` each wave loop is the serial
        ``fori_loop`` of :meth:`phase_fns`; above it, the steady-state loop
        commits group g-1 while computing group g.  The shuffle is the
        global barrier between the two loops and is the same at every
        depth.
        """
        W = self.cfg.num_workers if workers is None else int(workers)
        D = (getattr(self.cfg, "overlap_depth", 1)
             if depth is None else int(depth))
        if D < 1:
            raise ValueError(f"overlap depth must be >= 1, got {D}")
        M, R = self.M, self.R
        Weff_m = min(W * D, M)
        Weff_r = min(W * D, R)
        loop = self._serial_loop if D == 1 else self._software_pipeline
        prep = self._prep_fn()
        shuffle_step = self._shuffle_step_fn(
            W if self.shuffle.collective else 1
        )
        map_loop = loop(
            *self._map_wave(Weff_m, M), math.ceil(M / Weff_m), Weff_m,
            lambda splits, valid: self.initial_map_buffers(),
        )
        reduce_loop = loop(
            *self._reduce_wave(Weff_r, R), math.ceil(R / Weff_r), Weff_r,
            lambda pk, pv: self.initial_reduce_buffers(pk.shape[1]),
        )

        def phase_map(tokens):
            return map_loop(*prep(tokens))

        def phase_shuffle(bk, bv, bp):
            pk, pv, dropped, _, _ = shuffle_step(bk, bv, bp)
            return pk, pv, dropped

        def phase_reduce(pk, pv):
            return reduce_loop(pk, pv)

        fns = {"map": phase_map}
        if self.combiner:
            # The combine rides the compute side of the pipeline: pure
            # per-row work on the committed map buffers, ahead of the
            # global shuffle barrier (no commit state of its own).
            fns["combine"] = self._combine_step_fn()
        fns["shuffle"] = phase_shuffle
        fns["reduce"] = phase_reduce
        return {p: phases.scoped(p, f) for p, f in fns.items()}

    # ----------------------------------------- jitted steppers (per grant)

    def prep(self):
        if self._jit_prep is None:
            self._jit_prep = jax.jit(self._prep_fn())
        return self._jit_prep

    def map_stepper(self, W: int):
        # A grant wider than the task count slices/updates the identical
        # M-row window (the pad rows are write-through ballast), so every
        # W >= M is the same stepper: canonicalize the key to min(W, M).
        key = (min(int(W), self.M), self.combiner)
        if key not in self._jit_map:
            self._cache_misses += 1
            self._jit_map[key] = jax.jit(
                _step(*self._map_wave(key[0], self.M))
            )
        else:
            self._cache_hits += 1
        return self._jit_map[key]

    def combine_stepper(self):
        # W-independent barrier (like the lexsort shuffle): one entry.
        if self._jit_combine is None:
            self._cache_misses += 1
            self._jit_combine = jax.jit(self._combine_step_fn())
        else:
            self._cache_hits += 1
        return self._jit_combine

    def shuffle_stepper(self, W: int):
        key = (W if self.shuffle.collective else 1, self.combiner)
        if key not in self._jit_shuffle:
            self._cache_misses += 1
            self._jit_shuffle[key] = jax.jit(self._shuffle_step_fn(key[0]))
        else:
            self._cache_hits += 1
        return self._jit_shuffle[key]

    def reduce_stepper(self, W: int, cap: int):
        key = (min(int(W), self.R), cap, self.combiner)
        if key not in self._jit_reduce:
            self._cache_misses += 1
            self._jit_reduce[key] = jax.jit(
                _step(*self._reduce_wave(key[0], self.R))
            )
        else:
            self._cache_hits += 1
        return self._jit_reduce[key]

    def cache_info(self) -> dict:
        """Stepper-cache occupancy and hit/miss counters (regrant
        re-planning should mostly *hit*; equivalent grants share keys)."""
        return {
            "map_entries": len(self._jit_map),
            "combine_entries": int(self._jit_combine is not None),
            "shuffle_entries": len(self._jit_shuffle),
            "reduce_entries": len(self._jit_reduce),
            "pipelined_entries": len(self._jit_pipelined),
            "hits": self._cache_hits,
            "misses": self._cache_misses,
        }

    # ------------------------------------------------- phase compositions

    def phase_fns(self, workers: int | None = None) -> dict:
        """The pipeline as pure phase functions at one grant, each wave
        loop a serial ``fori_loop`` (:meth:`pipelined_phase_fns` at depth
        1).  Shared by the fused mode (composed under one jit), the traced
        mode (jitted and fenced per phase), and the XLA cost estimator
        (lowered per phase for abstract inputs).
        """
        return self.pipelined_phase_fns(workers, 1)

    # ---------------------------------------------------------------- modes

    def fused(self, workers: int | None = None, counters: bool = False):
        """Mode ``fused``: the whole pipeline under one ``jit`` — the
        zero-overhead hot path.  Returns ``job(tokens) -> (out_keys
        (R, cap), out_vals (R, cap), dropped ())``.  Works for both
        shuffle families (the collective one runs its emulated
        single-controller form; use :meth:`sharded` for a real mesh).

        With ``counters=True`` the job also returns ``stats``, the int32
        device scalars ``map.pairs``, ``combine.pairs`` (with the
        combiner), ``shuffle.slots`` / ``.pairs`` / ``.dropped`` and
        ``reduce.slots`` / ``.pairs`` / ``.segments``, counted in the same
        program, and the map's ``map.records`` (live records parsed) and
        ``map.slots`` (pair slots emitted and spill-sorted); the outputs
        are the same."""
        counts = self._counts if counters else None
        return jax.jit(_compose(self.phase_fns(workers), counts))

    def pipelined(self, workers: int | None = None,
                  depth: int | None = None, counters: bool = False):
        """Mode ``pipelined``: the fused pipeline with map and reduce
        waves software-pipelined at overlap depth D (default
        ``cfg.overlap_depth``) — wave group g's compute overlaps group
        g-1's commit inside one loop carry, prologue/epilogue included
        (see :meth:`pipelined_phase_fns`).  Fewer, wider loop iterations
        plus commit/compute overlap is where the wall-clock win comes
        from on wave-count-dominated (shuffle-heavy) configs.  Outputs
        are bit-exact vs :meth:`fused` by construction; jitted jobs are
        cached per ``(W, depth)`` grant.  ``counters=True`` adds the
        device counters, as in :meth:`fused`."""
        W = self.cfg.num_workers if workers is None else int(workers)
        D = (getattr(self.cfg, "overlap_depth", 1)
             if depth is None else int(depth))
        if D < 1:
            raise ValueError(f"overlap depth must be >= 1, got {D}")
        key = (W, D, self.combiner, bool(counters))
        if key in self._jit_pipelined:
            self._cache_hits += 1
            return self._jit_pipelined[key]
        self._cache_misses += 1
        counts = self._counts if counters else None
        jitted = jax.jit(_compose(self.pipelined_phase_fns(W, D), counts))
        self._jit_pipelined[key] = jitted
        return jitted

    def traced(self, recorder, workers: int | None = None,
               depth: int | None = None):
        """Mode ``traced``: phase-fenced stepper loops feeding a
        :class:`repro.telemetry.PhaseRecorder`.  Same semantics and
        outputs as :meth:`fused`.  Each phase is its own program, which
        also reduces the phase's counters on the device (only scalars
        reach the host), so conservation laws are checkable invariants
        rather than config-derived tautologies; each runs inside the host
        span ``mr.<phase>``, on the profiler's clock.

        With overlap depth D > 1 (``depth=`` or ``cfg.overlap_depth``)
        the map/reduce phases compile in their pipelined form and the
        trace gains a fourth ``"pipeline"`` phase carrying the
        cross-phase residual wall time (total minus the fenced phases)
        and the ``overlap_depth`` counter — so the timing conservation
        law still closes over the phase list.
        """
        D = (getattr(self.cfg, "overlap_depth", 1)
             if depth is None else int(depth))
        progs = {p: _counted(self._counts[p], f)
                 for p, f in self.pipelined_phase_fns(workers, D).items()}
        return self._recording_job(recorder, progs, self.meta(workers), D)

    def _recording_job(self, recorder, progs: dict, m: dict, depth: int = 1,
                       every: dict | None = None,
                       drops=lambda dropped: (dropped, {}, ()),
                       collect=lambda ok, ov: (ok, ov)):
        """The job of both fenced modes (:meth:`traced`, :meth:`sharded`
        with a recorder): runs the :func:`_counted` phase programs
        ``progs`` (the map's takes the job's tokens) one at a time, each
        fenced and wall-clocked, and records each phase with its device
        counters and the static facts ``m`` (:meth:`meta`) on a new trace
        of ``recorder``.

        ``every`` holds fields every phase record carries (a mesh's
        ``workers``).  ``drops(dropped)`` turns the shuffle's drop counts
        into the job's ``dropped``, the shuffle record's own fields, and a
        tuple the job returns after its three outputs.  ``collect(ok,
        ov)`` gives the reduce output as ``(R, cap)``.
        """
        every = every or {}
        app, cfg = self.app, self.cfg
        pair_bytes = phases.PAIR_BYTES

        def job(tokens):
            trace = recorder.start_job(app.name, cfg, m["input_len"])
            try:
                return _run(tokens, trace)
            except Exception:
                # A failed run must not leave a phantom/partial trace for
                # recorder.last / take_trace consumers to misread.
                if trace in recorder.traces:
                    recorder.traces.remove(trace)
                raise

        def _run(tokens, trace):
            t_job = _time.perf_counter()
            c = {}

            bufs, dt, cpu = _fenced("map", progs["map"], c, tokens)
            trace.record_phase(
                "map", dt,
                tasks=m["mappers"], waves=m["map_waves"], **every,
                records_in=c["map.records"],
                pairs_emitted=c["map.pairs"], pairs_capacity=c["map.slots"],
                cpu_s=cpu, cpu_workers=_NCPU,
            )

            if "combine" in progs:
                bufs, dt, cpu = _fenced("combine", progs["combine"], c,
                                        *bufs)
                trace.record_phase(
                    "combine", dt,
                    tasks=m["mappers"], **every,
                    pairs_in=c["map.pairs"], pairs_out=c["combine.pairs"],
                    bytes_in=c["map.pairs"] * pair_bytes,
                    bytes_out=c["combine.pairs"] * pair_bytes,
                    combine_capacity=m["combine_capacity"],
                    cpu_s=cpu, cpu_workers=_NCPU,
                    # Combining is map-local CPU work: it moves no fabric
                    # bytes (net_bytes == 0 is a checked invariant) — the
                    # contraction shows up in the *shuffle* counters.
                    net_bytes=0.0,
                )

            (pk, pv, dropped), dt, cpu = _fenced(
                "shuffle", progs["shuffle"], c, *bufs
            )
            dropped, fields, tail = drops(dropped)
            pairs_in = c["shuffle.pairs"]
            trace.record_phase(
                "shuffle", dt,
                pairs_in=pairs_in, pairs_out=c["reduce.pairs"],
                pairs_dropped=c["shuffle.dropped"],
                bytes_in=pairs_in * pair_bytes,
                bytes_out=c["reduce.pairs"] * pair_bytes,
                bytes_dropped=c["shuffle.dropped"] * pair_bytes,
                partitions=m["reducers"], **every,
                # The capacity the executed shuffle actually allocated
                # (the sharded mode substitutes the collective for the
                # configured shuffle).
                partition_capacity=int(pk.shape[-1]), **fields,
                cpu_s=cpu, cpu_workers=_NCPU,
                # Fabric accounting: every pair entering the shuffle
                # crosses the wire (dropped ones included) — post-combine
                # pairs when the combiner is on, which is exactly the
                # byte contraction the fabric sees.
                net_bytes=pairs_in * pair_bytes,
                net_s=dt,
            )

            (ok, ov), dt, cpu = _fenced("reduce", progs["reduce"], c,
                                        pk, pv)
            ok, ov = collect(ok, ov)
            trace.record_phase(
                "reduce", dt,
                tasks=m["reducers"], waves=m["reduce_waves"], **every,
                segments_out=c["reduce.segments"],
                segment_slots=c["reduce.slots"],
                cpu_s=cpu, cpu_workers=_NCPU,
            )

            total = _time.perf_counter() - t_job
            if depth > 1:
                # Overlap happens *inside* the fenced map/reduce phases
                # (their walls already absorb it), so the explicit
                # pipeline phase carries only the cross-phase residual —
                # conservation still closes over the phase list.  Host
                # bookkeeping moves no fabric bytes: net_bytes == 0 is a
                # checked invariant, not an omission.
                residual = max(0.0, total - trace.phase_time_sum())
                trace.record_phase(
                    "pipeline", residual,
                    overlap_depth=depth, net_bytes=0.0,
                )
            trace.finish(total)
            return (ok, ov, dropped, *tail)

        return job

    def resumable(self, recorder=None):
        """Mode ``resumable``: a :class:`repro.elastic.resumable.
        ResumableJob` whose wave steppers are this plan's (cursor and
        regrant bookkeeping live in the elastic layer; the pipeline
        lowering lives here, once)."""
        from repro.elastic.resumable import ResumableJob

        return ResumableJob.from_plan(self, recorder=recorder)

    # ------------------------------------------------------------- sharded

    def sharded(self, mesh, axis: str = "workers", counters: bool = False,
                recorder=None):
        """Mode ``sharded``: ``shard_map`` over the plan's own waves —
        workers are devices on ``mesh[axis]``, the shuffle a literal
        ``all_to_all``.  Worker w owns map tasks w, w+W, ... and reducers
        w, w+W, ...; it runs its ``waves_m`` tasks and its ``waves_r``
        partitions one at a time through the same compute/commit waves as
        every other mode, and its combine through the same
        ``combine_rows``.  This is the deployment path for real
        multi-chip meshes; semantics match every other mode.

        ``recorder=None`` compiles the fused single-program form (one
        dispatch, zero overhead).  With a recorder, the phases compile as
        *separate* mesh programs, run and recorded by the same fenced
        runner as :meth:`traced`'s; their records also carry ``workers``,
        and the shuffle's ``dropped_send`` / ``dropped_recv``.

        With ``counters=True`` the returned job additionally yields a
        ``stats`` dict reducing the per-worker overflow counters across
        shards (``dropped_send`` / ``dropped_recv`` /
        ``dropped_per_worker``) and, without a recorder, the device
        counters of :meth:`fused` summed over the workers.  The worker
        phases run under the same ``mr.<phase>`` scopes as every mode's
        (the input split with the map); the output gather after the
        reduce has none of its own.
        """
        cfg = self.cfg
        W = mesh.shape[axis]
        if cfg.num_workers != W:
            raise ValueError(
                f"cfg.num_workers={cfg.num_workers} != mesh {W}"
            )
        shuffle = self.shuffle
        if not shuffle.collective:
            # The sharded path's structural shuffle IS the mesh collective.
            shuffle = _backends.SHUFFLE_BACKENDS["all_to_all"]
        R, P = self.R, self.P
        waves_m = cfg.map_waves
        waves_r = cfg.reduce_waves

        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P_

        spec1 = P_(axis)  # the map's splits, of any rank
        spec2 = P_(axis, None)
        spec3 = P_(axis, None, None)
        replicated = NamedSharding(mesh, P_())

        def smap(worker_fn, in_specs, out_specs):
            # pallas_call has no replication rule; every output is
            # axis-sharded anyway, so the check adds nothing here.
            return jax.shard_map(
                worker_fn, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            )

        def prep(tokens):
            # Worker-major task layout: worker w owns tasks w, w+W, ...
            return tuple(
                jnp.swapaxes(x.reshape(waves_m, W, *x.shape[1:]), 0, 1)
                for x in self._split(tokens, waves_m * W)
            )

        # A worker's own task rows, one wave of one task at a time.
        map_loop = self._serial_loop(
            *self._map_wave(1, waves_m), waves_m, 1,
            lambda splits, valid: _buffers(_MAP_FILLS, waves_m, P),
        )
        reduce_loop = self._serial_loop(
            *self._reduce_wave(1, waves_r), waves_r, 1,
            lambda bk, bv: _buffers(_REDUCE_FILLS, waves_r, bk.shape[1]),
        )
        combine = self._combine_step_fn()

        # Local shards carry a leading worker axis of 1; between the
        # phases they are (1, waves_m, width) task rows, and the reduce's
        # (1, waves_r, cap) owned partitions.
        def w_map(splits, valid):
            return tuple(x[None] for x in map_loop(splits[0], valid[0]))

        def w_combine(k, v, pv):
            # Before any byte crosses the mesh: the collective built on
            # this worker's stream shrinks from waves_m*P to waves_m*Pc.
            return tuple(x[None] for x in combine(k[0], v[0], pv[0]))

        def w_shuffle(k, v, pv):
            bk, bv, dropped = shuffle.exchange(
                cfg, axis, k.reshape(-1), v.reshape(-1), pv.reshape(-1)
            )
            return bk[None], bv[None], dropped[None]

        def w_reduce(bk, bv):
            return tuple(x[None] for x in reduce_loop(bk[0], bv[0]))

        prep = phases.scoped("map", prep)
        wfns = {"map": w_map}
        if self.combiner:
            wfns["combine"] = w_combine
        wfns["shuffle"] = w_shuffle
        wfns["reduce"] = w_reduce
        wfns = {p: phases.scoped(p, f) for p, f in wfns.items()}

        def to_reducer_major(ok, ov):
            # Gather the worker shards first: folding the sharded worker
            # axis into the reducer axis has no sharding to carry on a
            # mesh with Explicit axes (jax.make_mesh's default).
            ok = jax.sharding.reshard(ok, replicated)
            ov = jax.sharding.reshard(ov, replicated)
            return _reducer_major(ok, R), _reducer_major(ov, R)

        def stats_from(per_worker: np.ndarray) -> dict:
            return {
                "dropped_send": int(per_worker[:, 0].sum()),
                "dropped_recv": int(per_worker[:, 1].sum()),
                "dropped_per_worker": per_worker,
            }

        if recorder is None:
            # Fused single mesh program (the zero-overhead deployment
            # path): all phases in one shard_map body, whose map reads
            # this worker's splits and their record validity.
            worker_job = _compose(wfns, _phase_counts(
                lambda splits, valid: valid) if counters else None)

            def worker(splits, valid):
                if not counters:
                    return worker_job(splits, valid)
                ok, ov, dropped, stats = worker_job(splits, valid)
                return ok, ov, dropped, {k: v[None]
                                         for k, v in stats.items()}

            out_specs = (spec3, spec3, spec2)
            if counters:
                out_specs += (P_(axis),)
            shard_fn = smap(worker, (spec1, spec1), out_specs)

            if not counters:
                def plain(tokens):
                    ok, ov, dropped = shard_fn(*prep(tokens))
                    ok, ov = to_reducer_major(ok, ov)
                    return ok, ov, dropped.sum()
                # jitted like fused(): callers may lower + compile ahead
                return jax.jit(plain)

            def whole(tokens):
                ok, ov, dropped, stats = shard_fn(*prep(tokens))
                ok, ov = to_reducer_major(ok, ov)
                # dropped: (W, 2) per-worker [send, recv] counters;
                # the device counters summed over the workers.
                return ok, ov, dropped, {k: v.sum()
                                         for k, v in stats.items()}

            jitted = jax.jit(whole)

            def with_counters(tokens):
                ok, ov, dropped, device_stats = jitted(tokens)
                per_worker = np.asarray(dropped)
                return ok, ov, dropped.sum(), {**stats_from(per_worker),
                                               **device_stats}

            return with_counters

        # Phase-fenced sharded execution: separate mesh programs, each
        # wall-clocked, its counters reduced across shards on the device.
        specs = {
            "map": ((spec1, spec1), (spec3,) * 3),
            "combine": ((spec3,) * 3, (spec3,) * 3),
            "shuffle": ((spec3,) * 3, (spec3, spec3, spec2)),
            "reduce": ((spec3, spec3), (spec3, spec3)),
        }
        mesh_fns = {p: smap(f, *specs[p]) for p, f in wfns.items()}
        map_fn = mesh_fns["map"]
        mesh_fns["map"] = lambda tokens: map_fn(*prep(tokens))
        progs = {p: _counted(self._counts[p], f)
                 for p, f in mesh_fns.items()}

        def drops(dropped):
            per_worker = np.asarray(dropped)  # (W, 2): [send, recv]
            stats = stats_from(per_worker)
            fields = {k: stats[k] for k in ("dropped_send", "dropped_recv")}
            return per_worker.sum(), fields, (stats,) if counters else ()

        return self._recording_job(
            recorder, progs, self.meta(W), every={"workers": W},
            drops=drops, collect=to_reducer_major,
        )
