"""Resumable execution: the plan's wave steppers + cursor bookkeeping.

:func:`repro.mapreduce.build_job` compiles map → shuffle → reduce as one
program; nothing can stop it mid-flight.  :class:`ResumableJob` drives
the **same** canonical wave steppers — the ones
:class:`repro.mapreduce.plan.ExecutionPlan` lowers once and every other
execution mode (fused / traced / sharded) derives from — one
wave-boundary step at a time, so a job can stop at any boundary,
snapshot, re-plan its remaining waves under a different worker grant W',
and resume **bit-identically**:

* **map** — one step runs the next W map tasks into the plan's (M, P)
  task-major accumulators.  A map task's output depends only on its
  split and the frozen config, never on W or on which wave ran it, so
  any wave re-grouping produces the same rows.
* **combine** — combiner jobs run one extra W-independent barrier step
  between map and shuffle: each task row is aggregated and compacted in
  place (the map accumulators shrink to the plan's combine capacity), so
  a job can be preempted either side of the combine and the cursor's
  ``combined`` flag says which side it stopped on.
* **shuffle** — one barrier step.  The ``lexsort`` backend partitions
  the canonical M·P pair stream with a *canonical* W-independent
  capacity, so even the overflow accounting is identical under any
  grant history.  The ``all_to_all`` backend's pack/unpack halves are
  vmapped over a worker axis with the literal collective replaced by
  the block transpose it implements — identical per-worker computation,
  single-controller execution, the capacity layout of a real W-device
  run at the grant held when the barrier executes.
* **reduce** — one step reduces the next W partitions through the
  configured :class:`~repro.mapreduce.backends.ReduceBackend` (row-
  independent by contract) into (R, cap) output accumulators.

This module owns only what is *elastic* about resumable execution: the
cursor lifecycle, grant changes, segment telemetry.  The pipeline
lowering lives in the plan — there is no private stepper copy here, so
resumable execution can never drift from the profiled modes.

Equivalences that follow (property-tested in ``tests/test_plan.py``):
preempt-at-every-boundary-then-resume ≡ fused ≡ traced, for every reduce
× shuffle backend combination; and for the ``lexsort`` shuffle the
results are bit-exact under *any* sequence of regrants.

Steppers are jit-compiled once per (grant, stage) and cached on the
plan — shared with every other consumer of the same plan — so
wave-stepped execution costs one dispatch per wave, not one compile.
"""

from __future__ import annotations

import dataclasses
import time as _time

import jax
import numpy as np

from repro.mapreduce import phases
from repro.mapreduce.engine import JobConfig, MapReduceApp  # noqa: F401
from repro.mapreduce.phases import PAD_KEY
from repro.mapreduce.plan import _NCPU, ExecutionPlan

from repro.elastic.snapshot import ElasticState, JobCursor


class ResumableJob:
    """One :class:`ExecutionPlan` compiled for wave-boundary stepping.

    ``cfg.num_workers`` is only the *initial* grant; the live grant rides
    in the cursor and per-grant steppers are compiled on demand.  The
    optional ``recorder`` (the :class:`repro.telemetry.PhaseRecorder`
    protocol) makes every :meth:`run` call emit one *segment trace*:
    per-phase wall times and measured counters covering exactly the waves
    that call executed, so per-phase models keep fitting on interrupted
    runs (merge segments with ``JobTrace.phase_times`` summing).
    """

    def __init__(self, app: MapReduceApp, cfg: JobConfig, input_len: int,
                 recorder=None, plan: ExecutionPlan | None = None):
        self.plan = plan if plan is not None else ExecutionPlan(
            app, cfg, input_len
        )
        self.app = self.plan.app
        self.cfg = self.plan.cfg
        self.input_len = self.plan.input_len
        self.recorder = recorder
        self.M = self.plan.M
        self.R = self.plan.R
        self.S = self.plan.S
        self.P = self.plan.P

    @classmethod
    def from_plan(cls, plan: ExecutionPlan, recorder=None) -> "ResumableJob":
        """The resumable *mode* of an existing plan (stepper caches
        shared with every other mode derived from it)."""
        return cls(plan.app, plan.cfg, plan.input_len,
                   recorder=recorder, plan=plan)

    # ------------------------------------------------------------ lifecycle

    def initial_state(self) -> ElasticState:
        cfg = self.cfg
        cursor = JobCursor(
            app=self.app.name, input_len=self.input_len,
            mappers=self.M, reducers=self.R, workers=cfg.num_workers,
            combiner=cfg.combiner, capacity_factor=cfg.capacity_factor,
            setup_rounds=cfg.setup_rounds, setup_dim=cfg.setup_dim,
            reduce_backend=cfg.reduce_backend,
            shuffle_backend=cfg.shuffle_backend,
        )
        bk, bv, bp = self.plan.initial_map_buffers()
        arrays = {"map_keys": bk, "map_vals": bv, "map_valid": bp}
        return ElasticState(cursor=cursor, arrays=arrays)

    def check_cursor(self, cursor: JobCursor) -> None:
        """A cursor must belong to this job (identity fields match)."""
        mine = self.initial_state().cursor
        for f in ("app", "input_len", "mappers", "reducers", "combiner",
                  "capacity_factor", "setup_rounds", "setup_dim",
                  "reduce_backend", "shuffle_backend"):
            if getattr(cursor, f) != getattr(mine, f):
                raise ValueError(
                    f"cursor field {f}={getattr(cursor, f)!r} does not "
                    f"match this job ({getattr(mine, f)!r})"
                )

    def regrant(self, state: ElasticState, workers: int) -> ElasticState:
        """Re-plan the remaining waves under a new grant.

        Legal at any wave boundary — which is everywhere, because states
        only exist at boundaries.  Buffers are canonical, so this is a
        pure cursor update; the next step compiles (or reuses) steppers
        for the new grant.
        """
        if workers < 1:
            raise ValueError("workers must be >= 1")
        return ElasticState(
            cursor=dataclasses.replace(state.cursor, workers=workers),
            arrays=state.arrays,
        )

    # ------------------------------------------------------------- stepping

    def step(self, state: ElasticState, tokens) -> ElasticState:
        """Execute exactly one wave-boundary step (map wave / shuffle
        barrier / reduce wave) under the cursor's current grant."""
        c = state.cursor
        if c.done:
            raise ValueError("job already complete")
        W = c.workers
        plan = self.plan
        arrays = dict(state.arrays)
        if not c.map_done:
            splits, svalid = plan.prep()(tokens)
            bk, bv, bp = plan.map_stepper(W)(
                splits, svalid,
                arrays["map_keys"], arrays["map_vals"], arrays["map_valid"],
                c.map_tasks_done,
            )
            arrays.update(map_keys=bk, map_vals=bv, map_valid=bp)
            cursor = dataclasses.replace(
                c,
                map_tasks_done=min(self.M, c.map_tasks_done + W),
                waves_executed=c.waves_executed + 1,
            )
        elif plan.combiner and not c.combined and not c.shuffled:
            # Map-side combine barrier: aggregate + compact the task rows
            # in place.  W-independent (pure per-row work), so the result
            # is identical under any grant history.
            ck, cv, cp = plan.combine_stepper()(
                arrays["map_keys"], arrays["map_vals"], arrays["map_valid"]
            )
            arrays.update(map_keys=ck, map_vals=cv, map_valid=cp)
            cursor = dataclasses.replace(
                c, combined=True, waves_executed=c.waves_executed + 1
            )
        elif not c.shuffled:
            pk, pv, dropped, ok, ov = plan.shuffle_stepper(W)(
                arrays["map_keys"], arrays["map_vals"], arrays["map_valid"]
            )
            # Map accumulators are fully absorbed into the partitions;
            # dropping them shrinks every post-shuffle snapshot.
            arrays = {
                "part_keys": pk, "part_vals": pv,
                "out_keys": ok, "out_vals": ov,
            }
            cursor = dataclasses.replace(
                c, shuffled=True, partition_cap=int(pk.shape[1]),
                dropped=int(dropped),
                waves_executed=c.waves_executed + 1,
            )
        else:
            ok, ov = plan.reduce_stepper(W, c.partition_cap)(
                arrays["part_keys"], arrays["part_vals"],
                arrays["out_keys"], arrays["out_vals"],
                c.reduce_tasks_done,
            )
            arrays.update(out_keys=ok, out_vals=ov)
            cursor = dataclasses.replace(
                c,
                reduce_tasks_done=min(self.R, c.reduce_tasks_done + W),
                waves_executed=c.waves_executed + 1,
            )
        return ElasticState(cursor=cursor, arrays=arrays)

    def run(self, tokens, state: ElasticState | None = None,
            preempt_after: int | None = None) -> ElasticState:
        """Run from ``state`` (or fresh) until done — or until
        ``preempt_after`` steps have executed *in this call*, leaving a
        wave-boundary state ready to snapshot/regrant/resume."""
        if state is None:
            state = self.initial_state()
        else:
            self.check_cursor(state.cursor)
        trace = None
        if self.recorder is not None:
            trace = self.recorder.start_job(
                self.app.name, self.cfg, self.input_len
            )
        executed = 0
        t_run = _time.perf_counter()
        try:
            while not state.cursor.done and (
                preempt_after is None or executed < preempt_after
            ):
                before = state.cursor
                before_arrays = state.arrays
                t0 = _time.perf_counter()
                c0 = _time.process_time()
                state = self.step(state, tokens)
                for leaf in state.arrays.values():
                    jax.block_until_ready(leaf)
                cpu = _time.process_time() - c0
                dt = _time.perf_counter() - t0
                executed += 1
                if trace is not None:
                    self._record_step(
                        trace, before, before_arrays, state, dt, cpu
                    )
        except Exception:
            if trace is not None and trace in self.recorder.traces:
                self.recorder.traces.remove(trace)
            raise
        if trace is not None:
            trace.finish(_time.perf_counter() - t_run)
        return state

    def result(self, state: ElasticState):
        """(out_keys (R, cap), out_vals (R, cap), dropped) of a done job."""
        if not state.cursor.done:
            raise ValueError(
                f"job not complete: {state.cursor.steps_remaining()} "
                "steps remain"
            )
        import jax.numpy as jnp

        return (
            state.arrays["out_keys"],
            state.arrays["out_vals"],
            jnp.int32(state.cursor.dropped),
        )

    # ----------------------------------------------------------- telemetry

    def _record_step(self, trace, before: JobCursor, before_arrays: dict,
                     after: ElasticState, wall_s: float,
                     cpu_s: float = 0.0) -> None:
        """One trace phase entry per executed step, counters measured from
        the actual buffers (same discipline as the engine's traced path).
        ``before_arrays`` is the pre-step buffer dict — the combine entry's
        ``pairs_in`` is the live count the barrier consumed, which only the
        pre-combine map accumulators still hold."""
        c_after = after.cursor
        if before.map_tasks_done != c_after.map_tasks_done:
            lo, hi = before.map_tasks_done, c_after.map_tasks_done
            pv = np.asarray(after.arrays["map_valid"][lo:hi])
            trace.record_phase(
                "map", wall_s,
                tasks=hi - lo, waves=1, workers=before.workers,
                pairs_emitted=int(pv.sum()),
                records_in=min(self.plan.rows, hi * self.S)
                - min(self.plan.rows, lo * self.S),
                cpu_s=cpu_s, cpu_workers=_NCPU,
            )
        elif before.combined != c_after.combined:
            pairs_in = int(np.asarray(before_arrays["map_valid"]).sum())
            pairs_out = int(np.asarray(after.arrays["map_valid"]).sum())
            pair_bytes = phases.PAIR_BYTES
            trace.record_phase(
                "combine", wall_s,
                tasks=self.M, waves=1, workers=before.workers,
                pairs_in=pairs_in, pairs_out=pairs_out,
                bytes_in=pairs_in * pair_bytes,
                bytes_out=pairs_out * pair_bytes,
                combine_capacity=self.plan.combine_cap,
                cpu_s=cpu_s, cpu_workers=_NCPU,
                net_bytes=0.0,  # combining is local: no fabric traffic
            )
        elif before.shuffled != c_after.shuffled:
            pairs_out = int(
                (np.asarray(after.arrays["part_keys"]) != int(PAD_KEY)).sum()
            )
            n_dropped = c_after.dropped
            pair_bytes = phases.PAIR_BYTES
            pairs_in = pairs_out + n_dropped
            trace.record_phase(
                "shuffle", wall_s,
                pairs_in=pairs_in, pairs_out=pairs_out,
                pairs_dropped=n_dropped,
                bytes_in=pairs_in * pair_bytes,
                bytes_out=pairs_out * pair_bytes,
                bytes_dropped=n_dropped * pair_bytes,
                partitions=self.R, workers=before.workers,
                partition_capacity=c_after.partition_cap,
                cpu_s=cpu_s, cpu_workers=_NCPU,
                net_bytes=pairs_in * pair_bytes,
                net_s=wall_s,
            )
        else:
            lo, hi = before.reduce_tasks_done, c_after.reduce_tasks_done
            seg = np.asarray(after.arrays["out_keys"][lo:hi])
            trace.record_phase(
                "reduce", wall_s,
                tasks=hi - lo, waves=1, workers=before.workers,
                segments_out=int((seg != int(PAD_KEY)).sum()),
                cpu_s=cpu_s, cpu_workers=_NCPU,
            )


def run_resumable(job: ResumableJob, tokens,
                  state: ElasticState | None = None,
                  preempt_after: int | None = None) -> ElasticState:
    """Run ``job`` from ``state`` (or fresh), preempting after
    ``preempt_after`` wave-boundary steps — module-level spelling of
    :meth:`ResumableJob.run` for the engine-integration entry point."""
    return job.run(tokens, state=state, preempt_after=preempt_after)
