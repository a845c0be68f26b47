"""Direct unit coverage of the shared phase primitives: segment reduction
with reduce_op='max', capacity-bounded bucket scatter, and partition
overflow accounting."""

import jax.numpy as jnp
import numpy as np
import pytest

import sorted_rows

from repro.mapreduce import JobConfig, get_shuffle_backend
from repro.mapreduce.phases import (
    PAD_KEY,
    bucket_scatter,
    hash_to_reducer,
    partition_capacity,
    segment_sum_sorted,
)


class TestSegmentSumSortedMax:
    def test_max_per_run(self):
        keys = jnp.asarray([1, 1, 1, 4, 4, 9], jnp.int32)
        vals = jnp.asarray([3, 7, 5, -2, -8, 0], jnp.int32)
        valid = jnp.ones(6, bool)
        ok, ov, first = segment_sum_sorted(keys, vals, valid, "max")
        np.testing.assert_array_equal(
            np.asarray(ok), [1, PAD_KEY, PAD_KEY, 4, PAD_KEY, 9]
        )
        np.testing.assert_array_equal(np.asarray(ov), [7, 0, 0, -2, 0, 0])
        np.testing.assert_array_equal(
            np.asarray(first), [1, 0, 0, 1, 0, 1]
        )

    def test_max_ignores_invalid_tail(self):
        keys = jnp.asarray([2, 2, PAD_KEY, PAD_KEY], jnp.int32)
        vals = jnp.asarray([-5, -9, 1000, 1000], jnp.int32)
        valid = keys != PAD_KEY
        ok, ov, _ = segment_sum_sorted(keys, vals, valid, "max")
        assert int(ov[0]) == -5  # poison values in padding never leak
        assert int(ok[1]) == int(PAD_KEY)

    def test_max_negative_values_not_clamped_to_zero(self):
        keys = jnp.asarray([3, 3], jnp.int32)
        vals = jnp.asarray([-7, -4], jnp.int32)
        ok, ov, _ = segment_sum_sorted(keys, vals, jnp.ones(2, bool), "max")
        assert int(ov[0]) == -4

    def test_unknown_op_rejected(self):
        keys = jnp.asarray([1], jnp.int32)
        with pytest.raises(ValueError):
            segment_sum_sorted(keys, keys, keys != PAD_KEY, "mean")


class TestSegmentSumSortedExact:
    """Every row of each case, as a 1-D call, against a numpy group-by."""

    @pytest.mark.parametrize("case", sorted_rows.CASES)
    @pytest.mark.parametrize("op", sorted_rows.OPS)
    def test_matches_numpy_group_by(self, op, case):
        keys, values = sorted_rows.case(case)
        for k, v in zip(keys, values):
            ok, ov, first = segment_sum_sorted(
                jnp.asarray(k), jnp.asarray(v), jnp.asarray(k != PAD_KEY), op
            )
            want_k, want_v, want_first = sorted_rows.group_by_row(k, v, op)
            assert ok.dtype == ov.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(ok), want_k)
            np.testing.assert_array_equal(np.asarray(ov), want_v)
            np.testing.assert_array_equal(np.asarray(first), want_first)


class TestBucketScatter:
    def test_exact_dropped_count(self):
        # 7 entries for bucket 0, capacity 4 -> exactly 3 dropped.
        ids = jnp.asarray([0] * 7 + [1] * 2, jnp.int32)
        vals = jnp.arange(9, dtype=jnp.int32)
        (out,), dropped = bucket_scatter(
            ids, 2, 2, 4, (vals,), (jnp.int32(-1),)
        )
        assert int(dropped) == 3
        np.testing.assert_array_equal(np.asarray(out[0]), [0, 1, 2, 3])
        np.testing.assert_array_equal(np.asarray(out[1]), [7, 8, -1, -1])

    def test_invalid_ids_not_counted_as_dropped(self):
        ids = jnp.asarray([0, 0, 5, 5, 5], jnp.int32)  # 5 >= n_buckets=2
        vals = jnp.arange(5, dtype=jnp.int32)
        (out,), dropped = bucket_scatter(
            ids, 2, 2, 1, (vals,), (jnp.int32(-1),)
        )
        assert int(dropped) == 1  # only the second bucket-0 entry
        np.testing.assert_array_equal(np.asarray(out), [[0], [-1]])

    def test_padding_rows_stay_at_fill(self):
        ids = jnp.asarray([0, 1], jnp.int32)
        vals = jnp.asarray([10, 20], jnp.int32)
        (out,), dropped = bucket_scatter(
            ids, 2, 4, 2, (vals,), (jnp.int32(-1),)
        )  # rows 2..3 are wave padding
        assert int(dropped) == 0
        np.testing.assert_array_equal(np.asarray(out[2:]), -np.ones((2, 2)))


class TestPartitionOverflowAccounting:
    def test_lexsort_dropped_is_exact(self):
        """All-one-key input: dropped must equal n_valid - capacity."""
        cfg = JobConfig(num_mappers=1, num_reducers=4, capacity_factor=1.0)
        n = 400
        keys = jnp.zeros((n,), jnp.int32)
        vals = jnp.ones((n,), jnp.int32)
        pvalid = jnp.ones((n,), bool)
        backend = get_shuffle_backend("lexsort")
        part_k, part_v, dropped = backend.partition(cfg, keys, vals, pvalid)
        cap = partition_capacity(n, 4, 1.0)
        assert int(dropped) == n - cap
        kept = int((np.asarray(part_k) != int(PAD_KEY)).sum())
        assert kept + int(dropped) == n  # conservation

    def test_generous_capacity_drops_nothing(self):
        cfg = JobConfig(num_mappers=1, num_reducers=4, capacity_factor=8.0)
        rng = np.random.default_rng(3)
        keys = jnp.asarray(rng.integers(0, 50, 300).astype(np.int32))
        vals = jnp.ones((300,), jnp.int32)
        backend = get_shuffle_backend("lexsort")
        _, _, dropped = backend.partition(
            cfg, keys, vals, jnp.ones((300,), bool)
        )
        assert int(dropped) == 0

    def test_partition_capacity_clamps(self):
        assert partition_capacity(100, 4, 1.0) == 25
        assert partition_capacity(100, 4, 100.0) == 100  # never beyond n
        assert partition_capacity(100, 1000, 1.0) == 1  # never below 1


class TestHashToReducer:
    def test_range_and_determinism(self):
        keys = jnp.arange(1000, dtype=jnp.int32)
        rid = np.asarray(hash_to_reducer(keys, 7))
        assert rid.min() >= 0 and rid.max() < 7
        np.testing.assert_array_equal(
            rid, np.asarray(hash_to_reducer(keys, 7))
        )

    def test_spreads_keys(self):
        keys = jnp.arange(10_000, dtype=jnp.int32)
        counts = np.bincount(np.asarray(hash_to_reducer(keys, 8)))
        assert counts.min() > 10_000 / 8 * 0.5  # no starved reducer
