"""Backend equivalence: every reduce backend and both shuffle backends must
produce identical job output (collect_results) and identical overflow
accounting (dropped) — the execution strategy is a timing axis, never a
semantics axis."""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sorted_rows

from repro.mapreduce import (
    JobConfig,
    MapReduceApp,
    PAD_KEY,
    REDUCE_BACKENDS,
    build_job,
    collect_results,
    exim_mainlog,
    eximparse,
    get_reduce_backend,
    wordcount,
    wordcount_corpus,
)

ALL_REDUCE = sorted(REDUCE_BACKENDS)

# (M, R, W, combiner) — exercises multi-wave map and reduce schedules.
CONFIG_GRID = [
    (1, 1, 1, False),
    (4, 3, 2, False),
    (7, 5, 3, True),
    (5, 8, 2, True),
]


def _job_output(app, corpus, **cfg_kwargs):
    cfg_kwargs.setdefault("capacity_factor", 8.0)
    cfg = JobConfig(**cfg_kwargs)
    ok, ov, dropped = build_job(app, cfg, len(corpus))(corpus)
    return collect_results(ok, ov), int(dropped)


class TestReduceBackendEquivalence:
    @pytest.mark.parametrize("M,R,W,combiner", CONFIG_GRID)
    def test_wordcount_identical_across_backends(self, M, R, W, combiner):
        corpus = wordcount_corpus(1500, vocab_size=211, seed=M * 10 + R)
        app = wordcount(211)
        ref = _job_output(app, corpus, num_mappers=M, num_reducers=R,
                          num_workers=W, combiner=combiner)
        assert ref[0] == dict(Counter(corpus.tolist()))
        for name in ALL_REDUCE:
            got = _job_output(app, corpus, num_mappers=M, num_reducers=R,
                              num_workers=W, combiner=combiner,
                              reduce_backend=name)
            assert got == ref, name

    @pytest.mark.parametrize("M,R,W,combiner", CONFIG_GRID)
    def test_eximparse_identical_across_backends(self, M, R, W, combiner):
        log = exim_mainlog(1800, n_transactions=40, seed=M + R)
        app = eximparse(40)
        ref = _job_output(app, log, num_mappers=M, num_reducers=R,
                          num_workers=W, combiner=combiner)
        for name in ALL_REDUCE:
            got = _job_output(app, log, num_mappers=M, num_reducers=R,
                              num_workers=W, combiner=combiner,
                              reduce_backend=name)
            assert got == ref, name

    def test_dropped_identical_under_skew(self):
        """Capacity overflow accounting must not depend on the backend."""
        corpus = np.zeros(600, dtype=np.int32)  # one key: max skew
        app = wordcount(16)
        results = {
            name: _job_output(app, corpus, num_mappers=2, num_reducers=4,
                              capacity_factor=1.0, reduce_backend=name)
            for name in ALL_REDUCE
        }
        ref = results[ALL_REDUCE[0]]
        assert ref[1] > 0  # skew actually overflows
        assert all(r == ref for r in results.values())


# Shuffle-backend equivalence (lexsort vs all_to_all, emulated vs real
# mesh, per-phase dropped counters) lives in tests/test_plan.py: both
# shuffle families are modes of one ExecutionPlan, so their agreement is
# asserted once by the mode-equivalence suite.


class TestBackendValidation:
    def test_unknown_reduce_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown reduce backend"):
            JobConfig(num_mappers=1, num_reducers=1, reduce_backend="nope")

    def test_unknown_shuffle_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown shuffle backend"):
            JobConfig(num_mappers=1, num_reducers=1, shuffle_backend="nope")

    def test_unsupported_reduce_op_rejected_at_build(self):
        """pallas is sum-only; a max-op app must fail fast, not mis-reduce."""
        app = MapReduceApp(
            name="maxapp", key_space=8,
            map_fn=lambda t, v: (t, t, v), reduce_op="max",
        )
        cfg = JobConfig(num_mappers=2, num_reducers=2,
                        reduce_backend="pallas")
        with pytest.raises(ValueError, match="supports"):
            build_job(app, cfg, 64)

    @pytest.mark.parametrize("combiner", [False, True])
    def test_pallas_partition_wider_than_max_c_rejected(self, combiner):
        """The kernels' (C, C) one-hot bounds C: a wider partition (or, with
        the combiner, a wider map-task row) raises a ValueError naming the
        limit instead of reaching the TPU compiler or another backend."""
        from repro.kernels.segment_reduce import MAX_C

        corpus = wordcount_corpus(MAX_C + 1, vocab_size=16, seed=0)
        cfg = JobConfig(num_mappers=1, num_reducers=1, combiner=combiner,
                        reduce_backend="pallas")
        job = build_job(wordcount(16), cfg, len(corpus))
        with pytest.raises(ValueError, match=f"C <= {MAX_C}"):
            job(corpus)

    @pytest.mark.parametrize("platform,interpret",
                             [("cpu", True), ("tpu", False), ("gpu", None)])
    def test_pallas_interprets_only_on_cpu(self, monkeypatch, platform,
                                           interpret):
        """Interpret mode is chosen only on the CPU platform; any platform
        other than cpu or tpu raises instead of silently interpreting."""
        from repro.mapreduce import backends

        monkeypatch.setattr(backends.jax, "default_backend", lambda: platform)
        if interpret is None:
            with pytest.raises(ValueError, match=repr(platform)):
                backends.pallas_interpret()
        else:
            assert backends.pallas_interpret() is interpret

    def test_get_reduce_backend_unknown_name(self):
        with pytest.raises(ValueError, match="registered"):
            get_reduce_backend("missing")


class TestPallasPrecisionBound:
    def test_exact_below_bound_lossy_above(self):
        """The float32 MXU accumulator is a documented contract: integer
        sums are exact below EXACT_INT_BOUND and lose low bits above it —
        pick a non-pallas backend for workloads near the bound."""
        import jax.numpy as jnp

        from repro.mapreduce.backends import PallasReduceBackend

        backend = PallasReduceBackend()
        bound = PallasReduceBackend.EXACT_INT_BOUND
        keys = jnp.asarray([[3, 3, PAD_KEY, PAD_KEY]], jnp.int32)
        below = jnp.asarray([[bound // 2, bound // 2 - 1, 0, 0]], jnp.int32)
        ok, ov = backend.reduce(keys, below, "sum")
        assert int(ov[0, 0]) == bound - 1  # exact below the bound
        above = jnp.asarray([[bound, 1, 0, 0]], jnp.int32)
        _, ov = backend.reduce(keys, above, "sum")
        assert int(ov[0, 0]) != bound + 1  # lossy above: 2**24 + 1 rounds


class TestMaxReduceOp:
    def test_max_app_end_to_end(self):
        """A reduce_op='max' app through jnp and xla backends."""
        rng = np.random.default_rng(5)
        corpus = rng.integers(0, 1_000, size=900).astype(np.int32)

        def map_fn(tokens, valid):
            import jax.numpy as jnp
            keys = jnp.where(valid, tokens % 13, PAD_KEY)
            vals = jnp.where(valid, tokens, jnp.iinfo(jnp.int32).min)
            return keys, vals.astype(jnp.int32), valid

        app = MapReduceApp(name="groupmax", key_space=13, map_fn=map_fn,
                           reduce_op="max")
        want = {}
        for t in corpus.tolist():
            want[t % 13] = max(want.get(t % 13, -(2 ** 31)), t)
        for backend in ("jnp", "xla"):
            cfg = JobConfig(num_mappers=5, num_reducers=3,
                            capacity_factor=8.0, reduce_backend=backend)
            ok, ov, dropped = build_job(app, cfg, len(corpus))(corpus)
            assert int(dropped) == 0
            # max aggregates may repeat per reducer slot row; collect the
            # per-key max rather than collect_results' summing gather.
            out_k = np.asarray(ok).ravel()
            out_v = np.asarray(ov).ravel()
            got = {}
            for k, v in zip(out_k, out_v):
                if int(k) != int(PAD_KEY):
                    got[int(k)] = max(got.get(int(k), -(2 ** 31)), int(v))
            assert got == want, backend


SCAN_BACKENDS = ("jnp", "xla")


class TestScanBackendsExact:
    """``reduce`` and ``combine`` of the scan backends against a plain
    numpy group-by, never against another backend."""

    @pytest.mark.parametrize("case", sorted_rows.CASES)
    @pytest.mark.parametrize("op", sorted_rows.OPS)
    @pytest.mark.parametrize("method", ["reduce", "combine"])
    @pytest.mark.parametrize("name", SCAN_BACKENDS)
    def test_matches_numpy_group_by(self, name, method, op, case):
        keys, values = sorted_rows.case(case)
        want = {"reduce": sorted_rows.group_by,
                "combine": sorted_rows.compacted}[method](keys, values, op)
        backend = get_reduce_backend(name)
        got = getattr(backend, method)(
            jnp.asarray(keys), jnp.asarray(values), op
        )
        for g, w in zip(got, want):
            assert g.dtype == jnp.int32 and g.shape == keys.shape
            np.testing.assert_array_equal(np.asarray(g), w)


class TestNoIndexMovement:
    """The scan backends aggregate and compact without moving data by
    index: their compiled programs hold no gather and no scatter, which on
    the TPU run at a small fraction of a scan's or a sort's rate."""

    @pytest.mark.parametrize("op", sorted_rows.OPS)
    @pytest.mark.parametrize("method", ["reduce", "combine"])
    @pytest.mark.parametrize("name", SCAN_BACKENDS)
    def test_compiled_hlo_has_no_gather_or_scatter(self, name, method, op):
        backend = get_reduce_backend(name)
        rows = jax.ShapeDtypeStruct((4, 256), jnp.int32)
        hlo = jax.jit(
            lambda k, v: getattr(backend, method)(k, v, op)
        ).lower(rows, rows).compile().as_text()
        for instruction in ("gather", "scatter"):
            assert f" {instruction}(" not in hlo, instruction
