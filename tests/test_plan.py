"""ExecutionPlan mode equivalence: the one property suite.

Every execution mode — fused, traced, resumable (preempted at *every*
wave boundary), and the sharded mesh path — derives from the same
lowered plan, so bit-exact agreement is a property of construction.
This suite checks it once, for every reduce × shuffle backend
combination, replacing the per-path equivalence copies that used to
live in ``test_backends.py`` / ``test_elastic.py``.
"""

import time as _time
from collections import Counter

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.elastic import load_snapshot, run_resumable, save_snapshot
from repro.mapreduce import (
    ExecutionPlan,
    JobConfig,
    REDUCE_BACKENDS,
    build_job,
    build_job_sharded,
    collect_results,
    wordcount,
    wordcount_corpus,
)
from repro.telemetry import PhaseRecorder

ALL_REDUCE = sorted(REDUCE_BACKENDS)
ALL_SHUFFLE = ("lexsort", "all_to_all")

CORPUS = wordcount_corpus(360, vocab_size=53, seed=9)
APP = wordcount(53)
WANT = dict(Counter(np.asarray(CORPUS).tolist()))


def _cfg(**kw):
    kw.setdefault("num_mappers", 5)
    kw.setdefault("num_reducers", 3)
    kw.setdefault("num_workers", 2)
    kw.setdefault("capacity_factor", 8.0)
    return JobConfig(**kw)


def _assert_same(a, b, ctx=None):
    ok_a, ov_a, d_a = a
    ok_b, ov_b, d_b = b
    assert np.array_equal(np.asarray(ok_a), np.asarray(ok_b)), ctx
    assert np.array_equal(np.asarray(ov_a), np.asarray(ov_b)), ctx
    assert int(d_a) == int(d_b), ctx


@pytest.mark.parametrize("reduce_backend", ALL_REDUCE)
@pytest.mark.parametrize("shuffle_backend", ALL_SHUFFLE)
class TestModeEquivalence:
    """fused == traced == resumable, bit-exact, per backend combination."""

    def test_fused_traced_resumable_bit_exact(self, reduce_backend,
                                              shuffle_backend):
        cfg = _cfg(reduce_backend=reduce_backend,
                   shuffle_backend=shuffle_backend)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        fused = plan.fused()(CORPUS)
        recorder = PhaseRecorder()
        traced = plan.traced(recorder)(CORPUS)
        job = plan.resumable()
        state = run_resumable(job, CORPUS)
        resumable = job.result(state)
        _assert_same(fused, traced, "traced")
        _assert_same(fused, resumable, "resumable")
        assert collect_results(fused[0], fused[1]) == WANT
        assert recorder.last.check_conservation() == []

    def test_preempt_every_boundary_bit_exact(self, reduce_backend,
                                              shuffle_backend):
        """Preempt after k steps then resume, for every k: identical
        outputs, counts, and merged-trace conservation laws — and all of
        it equal to the fused mode of the *same* plan."""
        cfg = _cfg(reduce_backend=reduce_backend,
                   shuffle_backend=shuffle_backend)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        ref = plan.fused()(CORPUS)
        recorder = PhaseRecorder()
        job = plan.resumable(recorder=recorder)
        ref_state = run_resumable(job, CORPUS)
        _assert_same(ref, job.result(ref_state), "uninterrupted")
        ref_trace = recorder.last
        total_steps = ref_state.cursor.waves_executed
        assert total_steps == 3 + 1 + 2  # map waves + shuffle + red waves
        for k in range(1, total_steps):
            recorder.clear()
            part = run_resumable(job, CORPUS, preempt_after=k)
            assert part.cursor.waves_executed == k
            assert not part.cursor.done
            full = run_resumable(job, CORPUS, state=part)
            _assert_same(ref, job.result(full), k)
            merged = _merge_segments(recorder.traces)
            assert merged.check_conservation() == [], k
            # Bit-exact counts: the interrupted run measured the same
            # phase totals as the uninterrupted one.
            for phase, name in (
                ("map", "pairs_emitted"),
                ("shuffle", "pairs_out"),
                ("shuffle", "pairs_dropped"),
                ("reduce", "segments_out"),
            ):
                assert merged.counter(phase, name) == ref_trace.counter(
                    phase, name
                ), (k, phase, name)


def _merge_segments(traces):
    """One trace holding all segment phases (conservation spans
    segments)."""
    from repro.telemetry import JobTrace

    merged = JobTrace(app=traces[0].app, config=dict(traces[0].config))
    for t in traces:
        merged.phases.extend(t.phases)
    merged.finish(sum(t.total_s for t in traces))
    return merged


class TestShardedEquivalence:
    """The real mesh mode against the single-controller modes (W=1 mesh
    in-process; the 4-device run lives in test_mapreduce_sharded)."""

    @pytest.fixture(scope="class")
    def mesh1(self):
        return jax.make_mesh((1,), ("workers",))

    @pytest.mark.parametrize("reduce_backend", ALL_REDUCE)
    def test_sharded_matches_fused_and_lexsort(self, mesh1, reduce_backend):
        corpus = wordcount_corpus(1200, vocab_size=97, seed=4)
        app = wordcount(97)
        want = dict(Counter(np.asarray(corpus).tolist()))
        lex_cfg = _cfg(num_mappers=4, num_workers=1,
                       reduce_backend=reduce_backend)
        lex = ExecutionPlan(app, lex_cfg, len(corpus)).fused()(corpus)
        cfg = _cfg(num_mappers=4, num_workers=1,
                   reduce_backend=reduce_backend,
                   shuffle_backend="all_to_all")
        plan = ExecutionPlan(app, cfg, len(corpus))
        emulated = plan.fused()(corpus)
        sharded = plan.sharded(mesh1)(corpus)
        _assert_same(emulated, sharded, reduce_backend)
        assert sharded[0].shape[0] == cfg.num_reducers
        assert collect_results(sharded[0], sharded[1]) == want
        # The two shuffle families agree on results + overflow counts.
        assert collect_results(lex[0], lex[1]) == want
        assert int(lex[2]) == int(sharded[2])

    @pytest.mark.parametrize("reduce_backend", ALL_REDUCE)
    def test_sharded_combiner_bit_exact(self, mesh1, reduce_backend):
        """The combine barrier runs before the collective too: the mesh
        mode of a combined plan matches the uncombined fused output."""
        corpus = wordcount_corpus(1200, vocab_size=97, seed=4)
        app = wordcount(97)
        want = dict(Counter(np.asarray(corpus).tolist()))
        cfg = _cfg(num_mappers=4, num_workers=1,
                   reduce_backend=reduce_backend,
                   shuffle_backend="all_to_all", combiner=True)
        plan = ExecutionPlan(app, cfg, len(corpus))
        ref = plan.fused()(corpus)
        sharded = plan.sharded(mesh1)(corpus)
        _assert_same(ref, sharded, reduce_backend)
        assert collect_results(sharded[0], sharded[1]) == want
        assert int(sharded[2]) == 0

    def test_sharded_dropped_matches_lexsort_under_skew(self, mesh1):
        corpus = np.zeros(600, dtype=np.int32)  # one key: max skew
        app = wordcount(16)
        lex_cfg = JobConfig(num_mappers=2, num_reducers=4, num_workers=1,
                            capacity_factor=1.0)
        lex = ExecutionPlan(app, lex_cfg, len(corpus)).fused()(corpus)
        cfg = JobConfig(num_mappers=2, num_reducers=4, num_workers=1,
                        capacity_factor=1.0, shuffle_backend="all_to_all")
        sharded = ExecutionPlan(app, cfg, len(corpus)).sharded(mesh1)(
            corpus
        )
        assert int(lex[2]) > 0  # skew actually overflows
        _assert_same(lex, sharded)

    def test_sharded_traced_per_phase_walls(self, mesh1):
        """The new capability: per-phase wall times + measured counters
        on the sharded path (three fenced mesh programs)."""
        cfg = _cfg(num_workers=1, shuffle_backend="all_to_all")
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        fused = plan.sharded(mesh1)(CORPUS)
        recorder = PhaseRecorder()
        traced = plan.sharded(mesh1, recorder=recorder)(CORPUS)
        _assert_same(fused, traced, "sharded traced")
        trace = recorder.last
        assert trace.phase_names() == ["map", "shuffle", "reduce"]
        assert all(p.wall_s > 0 for p in trace.phases)
        assert trace.check_conservation() == []
        assert trace.counter("map", "pairs_emitted") == len(CORPUS)
        assert trace.counter("shuffle", "dropped_send") == 0
        # One runner records both fenced modes: the same phases and
        # counters, and on the mesh only its named extras beside them.
        plan_recorder = PhaseRecorder()
        plan.traced(plan_recorder)(CORPUS)
        mine = plan_recorder.last
        assert mine.phase_names() == trace.phase_names()
        extras = {"shuffle": {"workers", "dropped_send", "dropped_recv"}}
        for got, want in zip(trace.phases, mine.phases):
            assert set(got.counters) == set(want.counters) | extras.get(
                got.phase, {"workers"}), got.phase

    def test_sharded_traced_counters_stats(self, mesh1):
        """recorder + counters=True compose: per-worker overflow stats
        ride alongside the per-phase trace."""
        corpus = np.zeros(600, dtype=np.int32)
        app = wordcount(16)
        cfg = JobConfig(num_mappers=2, num_reducers=4, num_workers=1,
                        capacity_factor=1.0, shuffle_backend="all_to_all")
        plan = ExecutionPlan(app, cfg, len(corpus))
        recorder = PhaseRecorder()
        ok, ov, dropped, stats = plan.sharded(
            mesh1, counters=True, recorder=recorder
        )(corpus)
        assert int(dropped) > 0
        assert stats["dropped_per_worker"].shape == (1, 2)
        assert stats["dropped_send"] + stats["dropped_recv"] == int(dropped)
        trace = recorder.last
        assert trace.counter("shuffle", "pairs_dropped") == int(dropped)
        assert trace.check_conservation() == []


class TestBuildJobWrappers:
    """build_job / build_job_sharded are thin mode selectors."""

    def test_build_job_routes_collective_with_recorder(self):
        mesh = jax.make_mesh((1,), ("workers",))
        cfg = _cfg(num_workers=1, shuffle_backend="all_to_all")
        recorder = PhaseRecorder()
        job = build_job(APP, cfg, len(CORPUS), mesh=mesh,
                        recorder=recorder)
        ok, ov, dropped = job(CORPUS)
        assert collect_results(ok, ov) == WANT
        assert recorder.last.phase_names() == ["map", "shuffle", "reduce"]

    def test_build_job_collective_still_requires_mesh(self):
        cfg = JobConfig(num_mappers=2, num_reducers=2,
                        shuffle_backend="all_to_all")
        with pytest.raises(ValueError, match="mesh"):
            build_job(wordcount(16), cfg, 100)

    def test_build_job_sharded_counters_contract(self):
        mesh = jax.make_mesh((1,), ("workers",))
        cfg = JobConfig(num_mappers=2, num_reducers=4, num_workers=1,
                        capacity_factor=1.0, shuffle_backend="all_to_all")
        corpus = np.zeros(600, dtype=np.int32)
        ok, ov, dropped, stats = build_job_sharded(
            wordcount(16), cfg, len(corpus), mesh, counters=True
        )(corpus)
        assert stats["dropped_send"] + stats["dropped_recv"] == int(dropped)

    def test_plan_validates_reduce_op_at_lowering(self):
        """pallas is sum-only; a max-op app must fail fast at plan
        construction, not mis-reduce."""
        from repro.mapreduce import MapReduceApp

        app = MapReduceApp(
            name="maxapp", key_space=8,
            map_fn=lambda t, v: (t, t, v), reduce_op="max",
        )
        cfg = JobConfig(num_mappers=2, num_reducers=2,
                        reduce_backend="pallas")
        with pytest.raises(ValueError, match="supports"):
            ExecutionPlan(app, cfg, 64)


class TestCanonicalCapacity:
    """The shuffle capacity is a property of the plan, not the grant."""

    def test_lexsort_capacity_grant_free(self):
        for W in (1, 2, 3, 5):
            cfg = _cfg(num_mappers=7, num_reducers=4, num_workers=W)
            plan = ExecutionPlan(APP, cfg, len(CORPUS))
            assert plan.partition_cap() == plan.lex_capacity
            ok, ov, dropped = plan.fused()(CORPUS)
            assert ok.shape == (4, plan.lex_capacity)
            assert collect_results(ok, ov) == WANT

    def test_grant_changes_never_change_lexsort_output(self):
        """W is a pure scheduling knob: any grant produces the identical
        (R, cap) output block — the invariant that makes fused == the
        wave-by-wave modes under arbitrary regrant histories."""
        ref = None
        for W in (1, 2, 3, 4, 7):
            cfg = _cfg(num_mappers=7, num_reducers=4, num_workers=W)
            out = ExecutionPlan(APP, cfg, len(CORPUS)).fused()(CORPUS)
            if ref is None:
                ref = out
            else:
                _assert_same(ref, out, W)

    def test_meta_shape_facts(self):
        cfg = _cfg(num_mappers=6, num_reducers=4, num_workers=2)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        m = plan.meta()
        assert m["mappers"] == 6 and m["reducers"] == 4
        assert m["map_waves"] == 3 and m["reduce_waves"] == 2
        assert m["n_pairs"] == plan.M * plan.P
        assert m["partition_capacity"] == plan.lex_capacity
        assert m["overlap_depth"] == 1


@pytest.mark.parametrize("reduce_backend", ALL_REDUCE)
@pytest.mark.parametrize("shuffle_backend", ALL_SHUFFLE)
class TestPipelinedEquivalence:
    """Mode ``pipelined`` is the fused lowering with a different
    schedule: bit-exact at every depth, for every backend combination,
    on ragged (W∤M) wave configurations."""

    def test_pipelined_bit_exact_vs_fused(self, reduce_backend,
                                          shuffle_backend):
        # The default fixture config is already ragged: M=5 over W=2
        # (3 map waves, last partial) and R=3 over W=2 (2 reduce waves).
        cfg = _cfg(reduce_backend=reduce_backend,
                   shuffle_backend=shuffle_backend)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        fused = plan.fused()(CORPUS)
        for depth in (1, 2, 3):
            out = plan.pipelined(depth=depth)(CORPUS)
            _assert_same(fused, out, (depth, "pipelined"))
            assert collect_results(out[0], out[1]) == WANT

    def test_pipelined_ragged_wave_groups(self, reduce_backend,
                                          shuffle_backend):
        """D∤waves and W∤M at once: the epilogue group is partial both
        in waves-per-group and tasks-per-wave."""
        cfg = _cfg(num_mappers=7, num_reducers=5, num_workers=3,
                   reduce_backend=reduce_backend,
                   shuffle_backend=shuffle_backend)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        fused = plan.fused()(CORPUS)
        for depth in (2, 3):
            _assert_same(
                fused, plan.pipelined(depth=depth)(CORPUS), depth
            )

    def test_traced_pipelined_records_pipeline_phase(self, reduce_backend,
                                                     shuffle_backend):
        cfg = _cfg(reduce_backend=reduce_backend,
                   shuffle_backend=shuffle_backend, overlap_depth=2)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        fused = plan.fused()(CORPUS)
        recorder = PhaseRecorder()
        traced = plan.traced(recorder)(CORPUS)  # depth from the config
        _assert_same(fused, traced, "traced depth=2")
        trace = recorder.last
        assert trace.phase_names() == ["map", "shuffle", "reduce",
                                       "pipeline"]
        assert trace.counter("pipeline", "overlap_depth") == 2
        assert trace.config["overlap_depth"] == 2
        assert trace.check_conservation() == []


@pytest.mark.parametrize("reduce_backend", ALL_REDUCE)
@pytest.mark.parametrize("shuffle_backend", ALL_SHUFFLE)
class TestCombinerEquivalence:
    """Map-side combining is a pure byte-contraction: for every
    commutative+associative app the combined plan is bit-exact against
    the uncombined one, in every execution mode, on a ragged (W∤M)
    wave configuration."""

    def test_all_modes_bit_exact_vs_uncombined(self, reduce_backend,
                                               shuffle_backend):
        # M=7 over W=2: 4 map waves, last partial — the ragged case.
        kw = dict(num_mappers=7, num_reducers=3, num_workers=2,
                  reduce_backend=reduce_backend,
                  shuffle_backend=shuffle_backend)
        base = ExecutionPlan(APP, _cfg(**kw), len(CORPUS)).fused()(CORPUS)
        plan = ExecutionPlan(APP, _cfg(combiner=True, **kw), len(CORPUS))
        ref = plan.fused()(CORPUS)
        # Against the uncombined plan: identical *results* (the combined
        # plan's output buffers are narrower — lex_capacity is sized
        # from the contracted stream — so padded shapes differ).
        assert collect_results(ref[0], ref[1]) == WANT
        assert collect_results(base[0], base[1]) == WANT
        assert int(ref[2]) == int(base[2]) == 0
        # Within the combined plan: every mode bit-exact vs its fused.
        recorder = PhaseRecorder()
        _assert_same(ref, plan.traced(recorder)(CORPUS), "traced")
        for depth in (2, 3):
            _assert_same(ref, plan.pipelined(depth=depth)(CORPUS),
                         (depth, "pipelined"))
        job = plan.resumable()
        _assert_same(ref, job.result(run_resumable(job, CORPUS)),
                     "resumable")
        # The traced run recorded the combine stage and its contraction,
        # and the combined trace satisfies every conservation law.
        trace = recorder.last
        assert "combine" in trace.phase_names()
        assert trace.check_conservation() == []
        assert trace.counter("combine", "pairs_in") == trace.counter(
            "map", "pairs_emitted"
        )
        assert trace.counter("combine", "pairs_out") <= trace.counter(
            "combine", "pairs_in"
        )
        assert trace.counter("shuffle", "pairs_in") == trace.counter(
            "combine", "pairs_out"
        )

    def test_preempt_every_boundary_with_combiner(self, reduce_backend,
                                                  shuffle_backend):
        """The combine barrier is a first-class preemption boundary:
        preempt after k steps then resume, for every k."""
        cfg = _cfg(reduce_backend=reduce_backend,
                   shuffle_backend=shuffle_backend, combiner=True)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        ref = plan.fused()(CORPUS)
        job = plan.resumable()
        total = run_resumable(job, CORPUS).cursor.waves_executed
        assert total == 3 + 1 + 1 + 2  # map + combine + shuffle + reduce
        for k in range(1, total):
            part = run_resumable(job, CORPUS, preempt_after=k)
            assert part.cursor.waves_executed == k
            assert not part.cursor.done
            full = run_resumable(job, CORPUS, state=part)
            _assert_same(ref, job.result(full), k)


class TestCombinerValidation:
    """Order-dependent reduce ops must be rejected at plan construction
    — a map-side combine would silently reorder their merges."""

    def test_combiner_rejects_order_dependent_op(self):
        from repro.mapreduce import MapReduceApp

        app = MapReduceApp(
            name="firstapp", key_space=8,
            map_fn=lambda t, v: (t, t, v), reduce_op="first",
        )
        cfg = JobConfig(num_mappers=2, num_reducers=2, combiner=True)
        with pytest.raises(ValueError, match="combiner"):
            ExecutionPlan(app, cfg, 64)
        # The same app lowers fine without the combiner.
        ExecutionPlan(
            app, JobConfig(num_mappers=2, num_reducers=2), 64
        )


class TestPipelinedRouting:
    """build_job routes overlap_depth; bad depths fail fast."""

    def test_build_job_routes_overlap_depth(self):
        ref = build_job(APP, _cfg(), len(CORPUS))(CORPUS)
        out = build_job(APP, _cfg(overlap_depth=3), len(CORPUS))(CORPUS)
        _assert_same(ref, out, "build_job depth=3")

    def test_config_validates_depth(self):
        with pytest.raises(ValueError, match="overlap_depth"):
            _cfg(overlap_depth=0)

    def test_plan_validates_depth(self):
        plan = ExecutionPlan(APP, _cfg(), len(CORPUS))
        with pytest.raises(ValueError, match="depth"):
            plan.pipelined(depth=0)


class TestPipelinedPreemption:
    """``resumable`` only materializes states at wave boundaries, so a
    snapshot taken while a pipelined job is preempted has — by
    construction — drained the in-flight wave group; resuming from any
    such snapshot (through a real checkpoint round trip) reproduces the
    pipelined output bit-exactly."""

    def test_snapshot_mid_pipeline_drains_in_flight_wave(self, tmp_path):
        cfg = _cfg(overlap_depth=3)
        plan = ExecutionPlan(APP, cfg, len(CORPUS))
        ref = plan.pipelined()(CORPUS)
        job = plan.resumable()
        total = run_resumable(job, CORPUS).cursor.waves_executed
        for k in range(1, total):
            part = run_resumable(job, CORPUS, preempt_after=k)
            mgr = CheckpointManager(str(tmp_path / f"k{k}"))
            save_snapshot(mgr, part)
            restored, _, _ = load_snapshot(mgr)
            full = run_resumable(job, CORPUS, state=restored)
            _assert_same(ref, job.result(full), k)


class TestStepperCaches:
    """Per-grant jit caches: equivalent grants share one stepper, and
    cache_info() exposes occupancy + hit/miss counters."""

    def test_equivalent_grants_share_steppers(self):
        plan = ExecutionPlan(APP, _cfg(), len(CORPUS))  # M=5, R=3
        # Any W >= M is the same map stepper (the regrant re-trace bug).
        assert plan.map_stepper(5) is plan.map_stepper(9)
        assert plan.map_stepper(2) is not plan.map_stepper(3)
        cap = plan.partition_cap()
        assert plan.reduce_stepper(3, cap) is plan.reduce_stepper(7, cap)
        info = plan.cache_info()
        assert info["map_entries"] == 3  # keys {5, 2, 3}
        assert info["reduce_entries"] == 1
        assert info["hits"] == 2
        assert info["misses"] == 4

    def test_combiner_flag_in_every_stepper_cache_key(self):
        """Combined and uncombined grants must never share a jitted
        trace (their buffer widths differ): the combine stepper is one
        W-independent entry, and every per-grant key carries the
        combiner flag."""
        on = ExecutionPlan(APP, _cfg(combiner=True), len(CORPUS))
        off = ExecutionPlan(APP, _cfg(), len(CORPUS))
        stepper = on.combine_stepper()
        assert on.combine_stepper() is stepper  # W-independent: cached
        assert on.cache_info()["combine_entries"] == 1
        assert on.cache_info()["hits"] == 1
        assert off.cache_info()["combine_entries"] == 0
        on.map_stepper(2)
        off.map_stepper(2)
        on.reduce_stepper(2, on.partition_cap())
        off.reduce_stepper(2, off.partition_cap())
        assert set(on._jit_map) == {(2, True)}
        assert set(off._jit_map) == {(2, False)}
        assert all(k[-1] is True for k in on._jit_reduce)
        assert all(k[-1] is False for k in off._jit_reduce)
        # The contraction is structural: the combined plan's partition
        # buffers are sized from the combined stream.
        assert on.meta()["combiner"] is True
        assert on.shuffle_width <= off.shuffle_width
        assert on.lex_capacity <= off.lex_capacity

    def test_pipelined_jobs_cached_per_grant_and_depth(self):
        plan = ExecutionPlan(APP, _cfg(), len(CORPUS))
        a = plan.pipelined(depth=2)
        assert plan.pipelined(depth=2) is a
        assert plan.pipelined(depth=3) is not a
        assert plan.pipelined(workers=3, depth=2) is not a
        assert plan.cache_info()["pipelined_entries"] == 3


@pytest.mark.slow
class TestPipelinedPerfSmoke:
    def test_depth2_not_slower_than_fused_beyond_noise(self):
        """On a shuffle-heavy (all_to_all, high wave count) config the
        pipelined schedule must at minimum not lose to fused beyond
        measurement noise; the real speedup target lives in
        benchmarks/pipeline_bench.py."""
        tokens = 8192
        corpus = wordcount_corpus(tokens, vocab_size=101, seed=3)
        app = wordcount(101)
        cfg = JobConfig(num_mappers=32, num_reducers=32, num_workers=2,
                        shuffle_backend="all_to_all", capacity_factor=8.0)
        plan = ExecutionPlan(app, cfg, tokens)

        def best(fn, reps=3):
            jax.block_until_ready(fn(corpus))  # compile + warm
            vals = []
            for _ in range(reps):
                t0 = _time.perf_counter()
                jax.block_until_ready(fn(corpus))
                vals.append(_time.perf_counter() - t0)
            return min(vals)

        t_fused = best(plan.fused())
        t_pipe = best(plan.pipelined(depth=2))
        assert t_pipe <= t_fused * 1.25, (t_pipe, t_fused)
