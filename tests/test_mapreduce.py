"""MapReduce engine correctness: wave scheduling, shuffle, reduce, apps."""

from collections import Counter

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.mapreduce import (
    JobConfig,
    build_job,
    collect_results,
    exim_mainlog,
    eximparse,
    wordcount,
    wordcount_corpus,
)


def _exim_oracle(log: np.ndarray) -> dict:
    """Total bytes per txn over every ``[txn, event, size]`` record."""
    want: dict[int, int] = {}
    for t, _, s in log.reshape(-1, 3).tolist():
        want[t] = want.get(t, 0) + s
    return want


class TestWordCount:
    @pytest.mark.parametrize("M,R", [(1, 1), (4, 3), (7, 5), (13, 2), (3, 11)])
    def test_matches_counter(self, M, R):
        corpus = wordcount_corpus(4000, vocab_size=257, seed=M * 100 + R)
        app = wordcount(257)
        cfg = JobConfig(num_mappers=M, num_reducers=R, capacity_factor=8.0)
        ok, ov, dropped = build_job(app, cfg, len(corpus))(corpus)
        assert int(dropped) == 0
        assert collect_results(ok, ov) == dict(Counter(corpus.tolist()))

    def test_combiner_equivalence(self):
        corpus = wordcount_corpus(4000, vocab_size=300, seed=7)
        app = wordcount(300)
        base = JobConfig(num_mappers=5, num_reducers=4, capacity_factor=8.0)
        comb = JobConfig(num_mappers=5, num_reducers=4, capacity_factor=8.0,
                         combiner=True)
        r1 = build_job(app, base, len(corpus))(corpus)
        r2 = build_job(app, comb, len(corpus))(corpus)
        assert collect_results(r1[0], r1[1]) == collect_results(r2[0], r2[1])

    def test_capacity_overflow_is_counted_not_silent(self):
        corpus = np.zeros(1000, dtype=np.int32)  # all one key: max skew
        app = wordcount(16)
        cfg = JobConfig(num_mappers=2, num_reducers=8, capacity_factor=1.0)
        ok, ov, dropped = build_job(app, cfg, len(corpus))(corpus)
        got = collect_results(ok, ov)
        assert int(dropped) > 0
        assert got[0] + int(dropped) == 1000  # conservation

    @given(
        n=st.integers(200, 2000),
        m=st.integers(1, 12),
        r=st.integers(1, 12),
        vocab=st.integers(2, 64),
        seed=st.integers(0, 1000),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_lossless_aggregation(self, n, m, r, vocab, seed):
        corpus = wordcount_corpus(n, vocab_size=vocab, seed=seed)
        app = wordcount(vocab)
        cfg = JobConfig(num_mappers=m, num_reducers=r, capacity_factor=16.0)
        ok, ov, dropped = build_job(app, cfg, len(corpus))(corpus)
        assert int(dropped) == 0
        got = collect_results(ok, ov)
        assert sum(got.values()) == n
        assert got == dict(Counter(corpus.tolist()))


class TestEximParse:
    # 2000 records: M=6 and M=9 cut 6000 tokens into splits that would start
    # inside a record if the log were split on tokens; M=7 leaves a
    # ragged last split
    @pytest.mark.parametrize("M,R", [(6, 4), (2, 9), (7, 5), (9, 3)])
    def test_per_transaction_bytes(self, M, R):
        log = exim_mainlog(6000, n_transactions=50, seed=3)
        app = eximparse(50)
        cfg = JobConfig(num_mappers=M, num_reducers=R, capacity_factor=8.0)
        ok, ov, dropped = build_job(app, cfg, len(log))(log)
        assert int(dropped) == 0
        assert collect_results(ok, ov) == _exim_oracle(log)

    @pytest.mark.parametrize("n", [5999, 6000, 6001, 6002])
    def test_mainlog_is_whole_records(self, n):
        """The generator keeps the whole records that fit in ``n``
        tokens."""
        rec = exim_mainlog(n, n_transactions=50, seed=3).reshape(-1, 3)
        assert len(rec) == n // 3
        assert ((0 <= rec[:, 0]) & (rec[:, 0] < 50)).all()
        assert ((200 <= rec[:, 2]) & (rec[:, 2] < 4000)).all()


class TestWaveScheduling:
    def test_wave_counts(self):
        cfg = JobConfig(num_mappers=10, num_reducers=7, num_workers=4)
        assert cfg.map_waves == 3
        assert cfg.reduce_waves == 2

    def test_bad_config_rejected(self):
        with pytest.raises(ValueError):
            JobConfig(num_mappers=0, num_reducers=1)
