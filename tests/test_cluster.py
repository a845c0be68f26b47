"""Cluster scheduling layer: workload, simulator, policies, online refit."""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.cluster import (
    AnalyticOracle,
    Cluster,
    Dispatch,
    JobSpec,
    POLICIES,
    Plan,
    assign_deadlines,
    generate_workload,
    get_policy,
)
from repro.cluster.policies import (
    StaticFIFO,
    _np_design,
    register_policy,
)
from repro.core.features import design_matrix, fit_feature_spec


# Small grids keep bootstrap profiling fast in tests.
FAST_GRIDS = dict(
    mapper_grid=(4, 8, 16),
    reducer_grid=(4, 8, 16),
    worker_grid=(2, 4),
    bootstrap_sizes=(1 << 13, 1 << 15, 1 << 17),
)


def fast_policy(name, **kwargs):
    return get_policy(name, seed=0, **FAST_GRIDS, **kwargs)


# ---------------------------------------------------------------------------
# Workload generation
# ---------------------------------------------------------------------------


class TestWorkload:
    def test_deterministic_and_sorted(self):
        a = generate_workload(30, seed=3)
        b = generate_workload(30, seed=3)
        assert a == b
        arr = [j.arrival for j in a]
        assert arr == sorted(arr) and arr[0] == 0.0
        assert generate_workload(30, seed=4) != a

    @pytest.mark.parametrize("arrival", ["poisson", "uniform", "bursty"])
    def test_arrival_processes(self, arrival):
        jobs = generate_workload(40, seed=0, arrival=arrival,
                                 mean_interarrival=0.5)
        assert len(jobs) == 40
        assert all(j.arrival >= 0 for j in jobs)

    def test_sizes_within_range_and_heterogeneous(self):
        jobs = generate_workload(50, seed=0, size_range=(1000, 64000))
        sizes = [j.size for j in jobs]
        assert min(sizes) >= 1000 and max(sizes) <= 64000
        assert len(set(j.app for j in jobs)) == 2

    def test_assign_deadlines(self):
        jobs = generate_workload(40, seed=0)
        est = lambda j: j.size * 1e-5  # noqa: E731
        with_dl = assign_deadlines(jobs, est, slack_range=(2.0, 3.0),
                                   fraction=0.5, seed=1)
        n_dl = sum(1 for j in with_dl if j.deadline is not None)
        assert 0 < n_dl < 40
        for j in with_dl:
            if j.deadline is not None:
                slack = (j.deadline - j.arrival) / est(j)
                assert 2.0 <= slack <= 3.0

    def test_bad_inputs(self):
        with pytest.raises(ValueError):
            generate_workload(0)
        with pytest.raises(ValueError):
            generate_workload(5, arrival="martian")
        with pytest.raises(ValueError):
            JobSpec(job_id=0, app="sort", size=100, arrival=0.0)


# ---------------------------------------------------------------------------
# Simulator invariants
# ---------------------------------------------------------------------------


class TestClusterSim:
    def test_fifo_accounting(self):
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(12, seed=0, mean_interarrival=0.05)
        res = Cluster(8, oracle).run(jobs, get_policy("fifo-static",
                                                      workers=4))
        m = res.metrics()
        assert m["n_completed"] == 12 and m["n_rejected"] == 0
        for r in res.records:
            assert r.start >= r.spec.arrival
            assert r.finish == pytest.approx(r.start + r.true_time)
        # FIFO never reorders: starts follow arrival order.
        starts = [r.start for r in res.records]
        assert starts == sorted(starts)
        assert 0.0 < m["utilization"] <= 1.0

    def test_concurrency_bounded_by_workers(self):
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(15, seed=1, mean_interarrival=0.01)
        total = 8
        res = Cluster(total, oracle).run(
            jobs, get_policy("fifo-static", workers=4)
        )
        events = []
        for r in res.records:
            events.append((r.start, r.plan.workers))
            events.append((r.finish, -r.plan.workers))
        # Sweep: completions release before same-time starts claim.
        events.sort(key=lambda e: (e[0], e[1]))
        in_use = 0
        for _, delta in events:
            in_use += delta
            assert 0 <= in_use <= total

    def test_oversized_plan_rejected(self):
        class Greedy(StaticFIFO):
            name = "greedy-test"

            def select(self, queue, free_workers, now):
                return Dispatch(queue[0], Plan("jnp", 8, 8, free_workers + 1))

        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(2, seed=0)
        with pytest.raises(ValueError, match="workers"):
            Cluster(4, oracle).run(jobs, Greedy())

    def test_stranded_jobs_fail_loudly(self):
        class Lazy(StaticFIFO):
            name = "lazy-test"

            def select(self, queue, free_workers, now):
                return None

        jobs = generate_workload(3, seed=0)
        with pytest.raises(RuntimeError, match="stranded"):
            Cluster(4, AnalyticOracle()).run(jobs, Lazy())


# ---------------------------------------------------------------------------
# Oracle
# ---------------------------------------------------------------------------


class TestAnalyticOracle:
    def test_deterministic_per_job_and_noise_isolation(self):
        o = AnalyticOracle(noise=0.05, seed=0)
        t1 = o.time("wordcount", "jnp", 1 << 16, 8, 8, 4, job_id=1)
        assert t1 == o.time("wordcount", "jnp", 1 << 16, 8, 8, 4, job_id=1)
        assert t1 != o.time("wordcount", "jnp", 1 << 16, 8, 8, 4, job_id=2)

    def test_wave_quantization_nonmonotonic(self):
        # More workers can't hurt; more mappers is non-monotonic (the
        # paper's central observation).
        o = AnalyticOracle(noise=0.0)
        t4 = o.time("wordcount", "jnp", 1 << 16, 16, 8, 4)
        t8 = o.time("wordcount", "jnp", 1 << 16, 16, 8, 8)
        assert t8 < t4
        times = [o.time("wordcount", "jnp", 1 << 16, m, 8, 4)
                 for m in (2, 8, 64, 512)]
        best = int(np.argmin(times))
        assert 0 < best < 3  # interior optimum in M

    def test_backend_crossover(self):
        # pallas (high launch overhead, best throughput) wins big jobs,
        # jnp wins small ones — the categorical knob matters.
        o = AnalyticOracle(noise=0.0)
        small = {b: o.time("wordcount", b, 1 << 12, 8, 8, 4)
                 for b in o.backends()}
        big = {b: o.time("wordcount", b, 1 << 20, 8, 8, 4)
               for b in o.backends()}
        assert min(small, key=small.get) == "jnp"
        assert min(big, key=big.get) == "pallas"


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------


def batch_trace(sizes, app="wordcount"):
    """All jobs arrive at t=0: pure ordering test bed."""
    return [
        JobSpec(job_id=i, app=app, size=s, arrival=0.0)
        for i, s in enumerate(sizes)
    ]


class TestPredictivePolicies:
    def test_registry(self):
        for name in ("fifo-static", "predict-fifo", "predict-sjf",
                     "predict-deadline"):
            assert name in POLICIES
        with pytest.raises(KeyError, match="unknown policy"):
            get_policy("lottery")
        with pytest.raises(ValueError, match="concrete name"):
            register_policy(type("Anon", (StaticFIFO,), {"name": "abstract"}))

    def test_np_design_matches_jnp_design_matrix(self):
        rng = np.random.default_rng(0)
        rows = rng.uniform(1, 40, size=(17, 4))
        spec = fit_feature_spec(rows, degree=3, cross_terms=True, scale=True)
        np.testing.assert_allclose(
            _np_design(spec, rows),
            np.asarray(design_matrix(spec, rows), dtype=np.float64),
            rtol=1e-5, atol=1e-6,
        )

    def test_bootstrap_fills_model_database(self):
        oracle = AnalyticOracle(noise=0.0)
        cluster = Cluster(8, oracle)
        pol = fast_policy("predict-sjf")
        pol.prepare(cluster, ["wordcount"])
        assert set(pol.db.backends_for("wordcount", oracle.platform)) == set(
            oracle.backends()
        )

    def test_sjf_dispatches_in_predicted_order(self):
        # Single-grant worker grid + 4-worker cluster: one job at a time,
        # so start order IS the policy's predicted-time order.
        oracle = AnalyticOracle(noise=0.0)
        jobs = batch_trace([1 << 17, 1 << 13, 1 << 15, 1 << 16, 1 << 14])
        pol = get_policy(
            "predict-sjf", seed=0,
            mapper_grid=(4, 8, 16), reducer_grid=(4, 8, 16),
            worker_grid=(4,), bootstrap_sizes=(1 << 13, 1 << 15, 1 << 17),
            online=False,
        )
        res = Cluster(4, oracle).run(jobs, pol)
        by_start = sorted(res.records, key=lambda r: r.start)
        preds = [r.plan.predicted_time for r in by_start]
        assert preds == sorted(preds)
        # Sanity: predicted order matches true-size order on this trace.
        assert [r.spec.size for r in by_start] == sorted(j.size for j in jobs)

    def test_deadline_policy_rejects_infeasible_admits_feasible(self):
        oracle = AnalyticOracle(noise=0.0)
        tight = JobSpec(job_id=0, app="wordcount", size=1 << 17,
                        arrival=0.0, deadline=0.01)  # impossible
        loose = JobSpec(job_id=1, app="wordcount", size=1 << 14,
                        arrival=0.0, deadline=60.0)
        res = Cluster(8, oracle).run([tight, loose],
                                     fast_policy("predict-deadline"))
        rec_tight, rec_loose = res.records
        assert not rec_tight.admitted
        assert "infeasible" in rec_tight.reject_reason
        assert rec_loose.completed and rec_loose.met_deadline
        assert res.metrics()["slo_attainment"] == 0.5

    def test_predictions_attached_before_dispatch(self):
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(8, seed=2, mean_interarrival=0.05)
        res = Cluster(8, oracle).run(jobs, fast_policy("predict-fifo"))
        for r in res.records:
            assert r.plan.predicted_time is not None
            assert r.plan.predicted_time > 0

    def test_online_refit_reduces_prediction_mae(self):
        # Coarse bootstrap (minimal sample count over the full config
        # space) + noise-free truth: the only error source is model
        # coarseness, which every completed job's observation chips away
        # at — so in-trace MAE must drop over the trace.
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(40, seed=5, mean_interarrival=0.05,
                                 size_range=(1 << 14, 1 << 18))
        kwargs = dict(seed=0, n_bootstrap=20)
        cluster = Cluster(8, oracle)
        online = cluster.run(jobs, get_policy("predict-sjf", online=True,
                                              **kwargs))
        m = online.metrics()
        assert m["pred_mae_pct_second_half"] < m["pred_mae_pct_first_half"]
        # ...and beats the frozen-model run on the same trace's second half.
        frozen = cluster.run(jobs, get_policy("predict-sjf", online=False,
                                              **kwargs))
        fm = frozen.metrics()
        assert (m["pred_mae_pct_second_half"]
                < fm["pred_mae_pct_second_half"])

    def test_seedless_refiner_demands_margin_before_replacing_model(self):
        # Warm-started from a saved db (no bootstrap profiles): live
        # observations alone must reach 2x the feature count before the
        # loaded model is replaced — clustered-config refits are too
        # rank-deficient to trust at bare determinacy.
        from repro.cluster.online import OnlineRefiner
        from repro.core.predictor import ModelDatabase
        from repro.core.regression import fit

        rng = np.random.default_rng(0)
        db = ModelDatabase()
        boot = rng.uniform(1, 40, size=(30, 2))
        db.put("wc", "plat", fit(boot, boot.sum(axis=1)), backend="jnp")
        ref = OnlineRefiner(db, "plat",
                            fit_kwargs=dict(degree=2, scale=True,
                                            lam=1e-6, cross_terms=False))
        n_feat = 1 + 2 * 2  # degree-2, 2 params, no cross terms
        before = db.get("wc", "plat", backend="jnp")
        refits = [
            ref.observe("wc", "jnp", rng.uniform(1, 40, size=2), float(i + 1))
            for i in range(2 * n_feat)
        ]
        assert not any(refits[: 2 * n_feat - 1])
        assert refits[-1]  # replaced only at the 2x margin
        assert db.get("wc", "plat", backend="jnp") is not before

    def test_online_refit_updates_database_model(self):
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(25, seed=6, mean_interarrival=0.05)
        pol = fast_policy("predict-sjf", n_bootstrap=20)
        Cluster(8, oracle).run(jobs, pol)
        assert pol.refiner.n_refits > 0
        assert pol._model_version > 0


class TestEngineOracleSmoke:
    def test_real_engine_trace(self):
        # The simulated cluster driving the *actual* MapReduce engine:
        # 2 tiny jobs, static FIFO (no bootstrap profiling -> 1 compile/job).
        from repro.cluster import EngineOracle

        oracle = EngineOracle()
        jobs = [
            JobSpec(job_id=0, app="wordcount", size=2048, arrival=0.0),
            JobSpec(job_id=1, app="eximparse", size=2048, arrival=0.0),
        ]
        res = Cluster(4, oracle).run(
            jobs, get_policy("fifo-static", mappers=4, reducers=4, workers=2)
        )
        assert res.metrics()["n_completed"] == 2
        assert all(r.true_time > 0 for r in res.records)

    def test_exim_job_at_a_size_that_is_not_whole_records(self):
        # 32768 tokens = 3 * 10922 + 2: the log is the 10922 whole records
        from repro.cluster import EngineOracle

        oracle = EngineOracle()
        assert oracle.time("eximparse", "xla", 32768, 4, 4, 2) > 0
        _, log = oracle._corpus("eximparse", 32768)
        assert len(log) == 32766


# ---------------------------------------------------------------------------
# Telemetry integration: traces, per-phase refits, resource-aware policy,
# queue-aware admission
# ---------------------------------------------------------------------------


class TestOracleTraces:
    def test_analytic_trace_matches_time(self):
        o = AnalyticOracle(noise=0.05, seed=3)
        t = o.time("wordcount", "jnp", 1 << 16, 8, 8, 4, job_id=7)
        trace = o.take_trace()
        assert trace is not None
        assert trace.phase_names() == ["map", "shuffle", "reduce"]
        assert trace.phase_time_sum() == pytest.approx(t, rel=1e-9)
        assert trace.check_conservation() == []

    def test_analytic_phase_profile_noise_free_and_sums(self):
        o = AnalyticOracle(noise=0.1, seed=0)
        prof = o.phase_profile("eximparse", "xla", 1 << 15, 8, 8, 4)
        assert set(prof["time_s"]) == {"map", "shuffle", "reduce"}
        assert sum(prof["time_s"].values()) == pytest.approx(
            o.time("eximparse", "xla", 1 << 15, 8, 8, 4, _noiseless=True)
        )
        assert prof["shuffle_bytes"] > 0
        # shuffle bytes scale with input size
        prof2 = o.phase_profile("eximparse", "xla", 1 << 16, 8, 8, 4)
        assert prof2["shuffle_bytes"] == pytest.approx(
            2 * prof["shuffle_bytes"]
        )

    def test_cluster_attaches_traces_to_records(self):
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(6, seed=1, mean_interarrival=0.05)
        res = Cluster(8, oracle).run(
            jobs, get_policy("fifo-static", workers=4)
        )
        for r in res.records:
            assert r.trace is not None
            assert r.trace.phase_time_sum() == pytest.approx(r.true_time)


class TestPerPhaseOnlineRefit:
    def test_observe_phases_publishes_resource_models(self):
        from repro.cluster.online import OnlineRefiner
        from repro.core.predictor import ModelDatabase

        rng = np.random.default_rng(0)
        db = ModelDatabase()
        ref = OnlineRefiner(
            db, "plat",
            phase_fit_kwargs=dict(degree=1, scale=True, lam=1e-6,
                                  cross_terms=False),
        )
        n_feat = 1 + 2  # degree-1, 2 params
        refit_seen = False
        for i in range(2 * n_feat + 1):
            row = rng.uniform(1, 40, size=2)
            refit_seen |= ref.observe_phases(
                "wc", "jnp", row,
                {"map": row[0] * 0.1, "shuffle": 1.0, "reduce": row[1]},
            )
        assert refit_seen and ref.n_phase_refits > 0
        assert set(db.resources_for("wc", "plat", "jnp")) == {
            "map:time_s", "shuffle:time_s", "reduce:time_s"
        }

    def test_policy_feeds_traces_to_phase_refiner(self):
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(40, seed=7, mean_interarrival=0.05)
        pol = fast_policy("predict-sjf")
        Cluster(8, oracle).run(jobs, pol)
        # every completion contributed phase rows (refits need volume, the
        # accumulation itself must always happen)
        total_rows = sum(
            len(v) for v in pol.refiner._phase_obs.values()
        )
        assert total_rows == 40 * 3  # 3 phases per analytic trace


class TestResourceAwarePolicy:
    def test_registered(self):
        assert "predict-resource" in POLICIES

    def test_default_identical_to_sjf(self):
        oracle = AnalyticOracle(noise=0.0)
        jobs = generate_workload(25, seed=3, mean_interarrival=0.05)
        cluster = Cluster(8, oracle)
        sjf = cluster.run(jobs, fast_policy("predict-sjf"))
        res = cluster.run(jobs, fast_policy("predict-resource"))
        # unconstrained fabric: decision-for-decision identical
        assert [r.start for r in res.records] == [
            r.start for r in sjf.records
        ]
        assert res.metrics()["makespan_s"] == pytest.approx(
            sjf.metrics()["makespan_s"]
        )

    def test_bootstrap_publishes_shuffle_bytes_models(self):
        from repro.telemetry.models import phase_resource_key

        oracle = AnalyticOracle(noise=0.0)
        pol = fast_policy("predict-resource")
        pol.prepare(Cluster(8, oracle), ["wordcount"])
        res_key = phase_resource_key("shuffle", "bytes")
        for b in oracle.backends():
            assert ("wordcount", oracle.platform, b, res_key) in pol.db
        # the bytes model tracks the oracle's linear size law (models
        # are keyed per combiner arm; default grid is combiner-off only)
        model = pol._bytes_models[("wordcount", "jnp", False)]
        from repro.cluster.policies import SIZE_UNIT, _np_predict

        lo = _np_predict(model, np.asarray([8, 8, 4, (1 << 14) / SIZE_UNIT]))
        hi = _np_predict(model, np.asarray([8, 8, 4, (1 << 16) / SIZE_UNIT]))
        assert hi[0] == pytest.approx(4 * lo[0], rel=0.05)

    def test_tight_capacity_defers_shuffle_heavy_jobs(self):
        # WordCount is shuffle-heavy (8 bytes/token at wordcount speed,
        # ~586 KB/s predicted at this size); EximParse moves a third of
        # the bytes over a longer run (~170 KB/s).  With a fabric budget
        # that fits one wordcount plus an eximparse but not two
        # wordcounts, the policy must dispatch the (slower-but-lighter)
        # eximparse job while the first wordcount runs, even though pure
        # SJF would pick the second wordcount.
        oracle = AnalyticOracle(noise=0.0)
        jobs = [
            JobSpec(job_id=0, app="wordcount", size=1 << 17, arrival=0.0),
            JobSpec(job_id=1, app="wordcount", size=1 << 17, arrival=0.0),
            JobSpec(job_id=2, app="eximparse", size=1 << 17, arrival=0.0),
        ]
        pol = get_policy(
            "predict-resource", seed=0, net_capacity=7e5,
            mapper_grid=(4, 8, 16), reducer_grid=(4, 8, 16),
            worker_grid=(2,), bootstrap_sizes=(1 << 13, 1 << 15, 1 << 17),
            online=False,
        )
        res = Cluster(4, oracle).run(jobs, pol)
        assert res.metrics()["n_completed"] == 3
        assert pol.n_contention_deferrals > 0
        by_start = sorted(res.records, key=lambda r: (r.start, r.spec.job_id))
        assert [r.spec.job_id for r in by_start] == [0, 2, 1]

    def test_net_capacity_validation(self):
        with pytest.raises(ValueError, match="net_capacity"):
            get_policy("predict-resource", net_capacity=0.0)


class TestQueueAwareAdmission:
    def grids(self, **kw):
        return dict(
            seed=0, mapper_grid=(4, 8, 16), reducer_grid=(4, 8, 16),
            worker_grid=(8,), bootstrap_sizes=(1 << 13, 1 << 15, 1 << 17),
            **kw,
        )

    def predicted_fastest(self, cluster, size):
        probe = get_policy("predict-deadline", **self.grids())
        probe.prepare(cluster, ["wordcount"])
        job = JobSpec(job_id=99, app="wordcount", size=size, arrival=0.0)
        return probe.best_plan(job, cluster.total_workers).predicted_time

    def test_queued_infeasible_job_rejected_up_front(self):
        oracle = AnalyticOracle(noise=0.0)
        cluster = Cluster(8, oracle)
        t_one = self.predicted_fastest(cluster, 1 << 17)
        # A is feasible and runs first (earlier deadline).  B's deadline
        # covers its own service time but not A's ahead of it: feasible at
        # dispatch, infeasible once queued.
        a = JobSpec(job_id=0, app="wordcount", size=1 << 17, arrival=0.0,
                    deadline=t_one * 1.5)
        b = JobSpec(job_id=1, app="wordcount", size=1 << 17, arrival=0.0,
                    deadline=t_one * 1.6)
        res = cluster.run([a, b], get_policy("predict-deadline",
                                             **self.grids()))
        rec_a, rec_b = res.records
        assert rec_a.admitted and rec_a.met_deadline
        assert not rec_b.admitted
        assert "queue wait" in rec_b.reject_reason
        # legacy behavior check: without queue awareness B looks feasible
        # at t=0 and is only rejected after its budget has burned down in
        # the queue (late rejection, no queue-wait term in the reason)
        res_off = cluster.run(
            [a, b], get_policy("predict-deadline", queue_aware=False,
                               **self.grids())
        )
        assert not res_off.records[1].admitted
        assert "queue wait" not in res_off.records[1].reject_reason

    def test_queued_but_feasible_job_admitted_and_meets(self):
        oracle = AnalyticOracle(noise=0.0)
        cluster = Cluster(8, oracle)
        t_one = self.predicted_fastest(cluster, 1 << 17)
        a = JobSpec(job_id=0, app="wordcount", size=1 << 17, arrival=0.0,
                    deadline=t_one * 1.5)
        b = JobSpec(job_id=1, app="wordcount", size=1 << 17, arrival=0.0,
                    deadline=t_one * 3.0)  # generous: survives the queue
        res = cluster.run([a, b], get_policy("predict-deadline",
                                             **self.grids()))
        rec_a, rec_b = res.records
        assert rec_a.admitted and rec_a.met_deadline
        assert rec_b.admitted and rec_b.met_deadline
        assert res.metrics()["slo_attainment"] == 1.0


class TestEngineOracleTraced:
    def test_traced_engine_jobs_carry_real_traces(self):
        from repro.cluster import EngineOracle

        oracle = EngineOracle(traced=True)
        jobs = [
            JobSpec(job_id=0, app="wordcount", size=2048, arrival=0.0),
            JobSpec(job_id=1, app="wordcount", size=2048, arrival=1000.0),
        ]
        res = Cluster(4, oracle).run(
            jobs, get_policy("fifo-static", mappers=4, reducers=4, workers=2)
        )
        for r in res.records:
            assert r.trace is not None
            assert r.trace.phase_names() == ["map", "shuffle", "reduce"]
            assert r.trace.check_conservation() == []
            # wall-clocked time is the traced job's outer total
            assert r.true_time > 0

    def test_untraced_phase_profile_keeps_time_untraced(self):
        from repro.cluster import EngineOracle

        oracle = EngineOracle()
        prof = oracle.phase_profile("wordcount", "jnp", 2048, 4, 4, 2)
        assert set(prof["time_s"]) == {"map", "shuffle", "reduce"}
        assert prof["shuffle_bytes"] > 0
        oracle.time("wordcount", "jnp", 2048, 4, 4, 2)
        assert oracle.take_trace() is None


class TestQueueAwareParallelism:
    def test_concurrently_feasible_jobs_not_rejected(self):
        # Two deadline jobs whose grants fit the pool side by side must
        # both be admitted: neither actually queues behind the other, so
        # the sweep's virtual pool must not count phantom wait.
        oracle = AnalyticOracle(noise=0.0)
        cluster = Cluster(16, oracle)
        probe = get_policy(
            "predict-deadline", seed=0, mapper_grid=(4, 8, 16),
            reducer_grid=(4, 8, 16), worker_grid=(8,),
            bootstrap_sizes=(1 << 13, 1 << 15, 1 << 17),
        )
        probe.prepare(cluster, ["wordcount"])
        t_one = probe.best_plan(
            JobSpec(job_id=99, app="wordcount", size=1 << 17, arrival=0.0),
            16,
        ).predicted_time
        jobs = [
            JobSpec(job_id=i, app="wordcount", size=1 << 17, arrival=0.0,
                    deadline=t_one * 1.3)
            for i in range(2)
        ]
        res = cluster.run(jobs, get_policy(
            "predict-deadline", seed=0, mapper_grid=(4, 8, 16),
            reducer_grid=(4, 8, 16), worker_grid=(8,),
            bootstrap_sizes=(1 << 13, 1 << 15, 1 << 17),
        ))
        assert all(r.admitted for r in res.records)
        assert res.metrics()["slo_attainment"] == 1.0


class TestPhaseRefitCadence:
    def test_phase_refits_run_at_slower_cadence(self):
        from repro.cluster.online import OnlineRefiner
        from repro.core.predictor import ModelDatabase

        rng = np.random.default_rng(1)
        ref = OnlineRefiner(
            ModelDatabase(), "plat", refit_every=1,
            phase_fit_kwargs=dict(degree=1, scale=True, lam=1e-6,
                                  cross_terms=False),
        )
        assert ref.phase_refit_every == 5
        refits = [
            ref.observe_phases("wc", "jnp", rng.uniform(1, 40, size=2),
                               {"map": 1.0})
            for _ in range(40)
        ]
        # plenty of data, but at most one refit per 5 completions
        assert 0 < sum(refits) <= 40 // 5
        with pytest.raises(ValueError, match="phase_refit_every"):
            OnlineRefiner(ModelDatabase(), "plat", phase_refit_every=0)
