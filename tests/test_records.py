"""Record-granular map output: an app that declares its record width is
split on record boundaries, with any row count and any M, and its map emits
``pairs_per_record`` pair slots per record slot, in every mode.

The apps under test are the UserVisits aggregation (``SELECT sourceIP,
SUM(adRevenue) GROUP BY sourceIP``) over seeded tables of 32-token rows,
checked against a numpy GROUP BY SUM, and Exim mainlog parsing over
3-token records, checked against the sizes summed per transaction id.
"""

import math
import subprocess
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from repro.mapreduce import (
    ExecutionPlan,
    JobConfig,
    collect_results,
    exim_mainlog,
    eximparse,
    uservisits,
    wordcount,
    wordcount_corpus,
)
from repro.telemetry import PhaseRecorder

ROOT = Path(__file__).resolve().parents[1]
WIDTH, IP, REVENUE, KEYS = 32, 0, 14, 512
APP = uservisits(KEYS, WIDTH, IP, REVENUE)


def _table(rows: int, seed: int = 3) -> np.ndarray:
    """``rows`` seeded rows of random tokens, flat, with sourceIP ids below
    ``KEYS`` and adRevenue cents in [1, 100000]."""
    rng = np.random.default_rng(seed)
    t = rng.integers(-2**31, 2**31, size=(rows, WIDTH), dtype=np.int64)
    t[:, IP] = rng.integers(0, KEYS, rows)
    t[:, REVENUE] = rng.integers(1, 100_001, rows)
    return t.astype(np.int32).reshape(-1)


def _group_by_sum(table: np.ndarray) -> dict[int, int]:
    rows = table.reshape(-1, WIDTH)
    sums = np.bincount(rows[:, IP], weights=rows[:, REVENUE].astype(
        np.float64), minlength=KEYS)
    present = np.bincount(rows[:, IP], minlength=KEYS) > 0
    return {int(k): int(sums[k]) for k in np.flatnonzero(present)}


def _plan(rows: int, M: int, **kw) -> ExecutionPlan:
    cfg = JobConfig(num_mappers=M, num_reducers=kw.pop("R", 5), **kw)
    return ExecutionPlan(APP, cfg, rows * WIDTH)


# rows not a multiple of M; M > rows / 2; splits whose tokens fill whole
# vector registers (the lane-tiled split)
SIZES = [(2003, 7), (2003, 20), (2003, 1500), (2000, 5)]


def _run(plan, mode, table):
    if mode == "fused":
        return plan.fused()(table)
    if mode == "pipelined":
        return plan.pipelined(depth=2)(table)
    if mode == "traced":
        return plan.traced(PhaseRecorder())(table)
    job = plan.resumable()
    return job.result(job.run(table))


@pytest.mark.parametrize("mode", ["fused", "pipelined", "traced",
                                  "resumable"])
@pytest.mark.parametrize("rows,M", SIZES)
def test_every_mode_gives_the_group_by_sum(rows, M, mode):
    table = _table(rows)
    ok, ov, dropped = _run(_plan(rows, M, num_workers=3), mode, table)
    assert int(dropped) == 0
    assert collect_results(ok, ov) == _group_by_sum(table)


@pytest.mark.parametrize("rows,M", SIZES)
def test_splits_are_whole_records(rows, M):
    plan = _plan(rows, M)
    S = math.ceil(rows / M)
    assert plan.rows == rows and plan.S == S and plan.P == S
    splits, valid = plan.prep()(_table(rows))
    assert splits.shape[0] == M and splits.size == M * S * WIDTH
    assert valid.shape == (M, S) and int(valid.sum()) == rows
    assert np.asarray(valid).ravel()[:rows].all()
    # a lane-tiled split where the split's tokens fill whole registers
    tiled = S * WIDTH % 128 == 0
    assert splits.shape[1:] == ((S * WIDTH // 128, 128) if tiled
                                else (S * WIDTH,))
    m = plan.meta()
    assert (m["records"], m["record_width"], m["split_size"],
            m["n_pairs"]) == (rows, WIDTH, S, M * S)


@pytest.mark.parametrize("mode", ["fused", "pipelined"])
@pytest.mark.parametrize("rows,M", SIZES)
def test_map_counts_records_and_slots(rows, M, mode):
    plan = _plan(rows, M)
    table = _table(rows)
    job = plan.fused(counters=True) if mode == "fused" else \
        plan.pipelined(depth=2, counters=True)
    *_, stats = job(table)
    c = {k: int(v) for k, v in stats.items()}
    assert c["map.slots"] == M * math.ceil(rows / M)
    assert c["map.records"] == rows == c["map.pairs"]
    assert c["shuffle.pairs"] == rows and c["shuffle.dropped"] == 0


def test_traced_map_records_records_and_slots():
    rows, M = 2003, 7
    recorder = PhaseRecorder()
    _plan(rows, M).traced(recorder)(_table(rows))
    t = recorder.last
    assert t.counter("map", "records_in") == rows
    assert t.counter("map", "pairs_capacity") == M * math.ceil(rows / M)
    assert t.counter("map", "pairs_emitted") == rows
    assert t.check_conservation() == []


def test_resumable_regrants_mid_map():
    rows, M = 2003, 7
    table = _table(rows)
    job = _plan(rows, M, num_workers=2).resumable()
    state = job.run(table, preempt_after=2)
    state = job.regrant(state, 3)
    ok, ov, dropped = job.result(job.run(table, state=state))
    assert int(dropped) == 0
    assert collect_results(ok, ov) == _group_by_sum(table)


def test_input_must_be_whole_records():
    cfg = JobConfig(num_mappers=2, num_reducers=2)
    with pytest.raises(ValueError, match="whole records"):
        ExecutionPlan(APP, cfg, 10 * WIDTH + 3)
    with pytest.raises(ValueError, match="3001 tokens is not whole records "
                                         "of 3 tokens"):
        ExecutionPlan(eximparse(50), cfg, 3001)


@pytest.mark.parametrize("col", [-1, WIDTH])
def test_columns_lie_inside_a_row(col):
    with pytest.raises(ValueError, match="outside a row"):
        uservisits(KEYS, WIDTH, col, REVENUE)


def test_width_one_is_token_granular():
    """A width-1 app splits its tokens as before: M splits of
    ``ceil(n / M)`` tokens, one pair slot each, a 2-D split."""
    corpus = wordcount_corpus(1001, vocab_size=37, seed=2)
    cfg = JobConfig(num_mappers=7, num_reducers=3, num_workers=2)
    plan = ExecutionPlan(wordcount(37), cfg, len(corpus))
    S = math.ceil(len(corpus) / 7)
    assert plan.meta() == {
        "input_len": 1001, "record_width": 1, "records": 1001,
        "mappers": 7, "reducers": 3, "workers": 2, "split_size": S,
        "map_waves": 4, "reduce_waves": 2, "n_pairs": 7 * S,
        "combiner": False, "combine_capacity": 37, "shuffle_width": S,
        "partition_capacity": min(math.ceil(7 * S / 3 * 4.0), 7 * S),
        "r_pad": 3, "overlap_depth": 1,
    }
    splits, valid = plan.prep()(corpus)
    assert splits.shape == valid.shape == (7, S)
    ok, ov, dropped, stats = plan.fused(counters=True)(corpus)
    assert int(dropped) == 0
    assert collect_results(ok, ov) == dict(Counter(corpus.tolist()))
    assert int(stats["map.records"]) == 1001
    assert int(stats["map.slots"]) == 7 * S


EXIM = eximparse(1024)
# (records, M): splits of 128 records, whose 384 tokens fill whole vector
# registers (the lane-tiled split); 7 full flat splits of 286 records; a
# ragged last split
EXIM_SIZES = [(640, 5), (2002, 7), (2000, 7)]


def _mainlog(records: int) -> np.ndarray:
    return exim_mainlog(3 * records, n_transactions=1024, seed=records)


def _per_txn(log: np.ndarray) -> dict[int, int]:
    rec = log.reshape(-1, 3)
    sums = np.bincount(rec[:, 0], weights=rec[:, 2], minlength=1024)
    return {int(k): int(sums[k]) for k in np.unique(rec[:, 0])}


def _exim_plan(records: int, M: int, **kw) -> ExecutionPlan:
    cfg = JobConfig(num_mappers=M, num_reducers=5, **kw)
    return ExecutionPlan(EXIM, cfg, 3 * records)


@pytest.mark.parametrize("mode", ["fused", "pipelined", "traced",
                                  "resumable"])
@pytest.mark.parametrize("records,M", EXIM_SIZES)
def test_exim_every_mode_gives_the_fused_output(records, M, mode):
    log = _mainlog(records)
    plan = _exim_plan(records, M, num_workers=3)
    ok, ov, dropped = _run(plan, mode, log)
    assert int(dropped) == 0
    assert collect_results(ok, ov) == _per_txn(log)
    want = plan.fused()(log)
    assert np.array_equal(np.asarray(ok), np.asarray(want[0]))
    assert np.array_equal(np.asarray(ov), np.asarray(want[1]))


@pytest.mark.parametrize("records,M", EXIM_SIZES)
def test_exim_emits_one_pair_slot_per_record(records, M):
    plan = _exim_plan(records, M)
    assert plan.P == math.ceil(records / M)
    splits, valid = plan.prep()(_mainlog(records))
    tiled = plan.P * 3 % 128 == 0
    assert splits.shape == ((M, plan.P * 3 // 128, 128) if tiled
                            else (M, plan.P * 3))
    assert valid.shape == (M, plan.P)
    *_, stats = plan.fused(counters=True)(_mainlog(records))
    c = {k: int(v) for k, v in stats.items()}
    assert c["map.slots"] == M * plan.P == c["shuffle.slots"]
    assert c["map.records"] == records == c["map.pairs"]
    if records % M == 0:
        assert c["map.pairs"] == c["map.slots"]


_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
import jax
import numpy as np
sys.path.insert(0, {tests!r})
from test_records import APP, WIDTH, EXIM, _group_by_sum, _mainlog, \
    _per_txn, _table
from repro.mapreduce import ExecutionPlan, JobConfig, collect_results

for W, rows, M in [(1, 2003, 7), (2, 2003, 9), (4, 2003, 6), (4, 2000, 8),
                   (4, 101, 60)]:
    mesh = jax.make_mesh((W,), ("workers",), devices=jax.devices()[:W])
    cfg = JobConfig(num_mappers=M, num_reducers=5, num_workers=W,
                    capacity_factor=8.0, shuffle_backend="all_to_all")
    plan = ExecutionPlan(APP, cfg, rows * WIDTH)
    table = _table(rows)
    ok, ov, dropped, stats = plan.sharded(mesh, counters=True)(table)
    assert int(dropped) == 0, (W, rows, M)
    assert collect_results(ok, ov) == _group_by_sum(table), (W, rows, M)
    # the mesh maps whole waves: ceil(M / W) * W splits
    M_pad = -(-M // W) * W
    assert int(stats["map.slots"]) == M_pad * plan.S, (W, rows, M)
    assert int(stats["map.records"]) == rows == int(stats["map.pairs"])
# Exim's 3-token records, lane-tiled and flat: the mesh job's output is the
# emulated collective's, bit for bit
for W, records, M in [(4, 640, 5), (2, 2002, 7), (4, 2000, 7)]:
    mesh = jax.make_mesh((W,), ("workers",), devices=jax.devices()[:W])
    cfg = JobConfig(num_mappers=M, num_reducers=5, num_workers=W,
                    capacity_factor=8.0, shuffle_backend="all_to_all")
    plan = ExecutionPlan(EXIM, cfg, 3 * records)
    log = _mainlog(records)
    ok, ov, dropped = plan.sharded(mesh)(log)
    assert int(dropped) == 0, (W, records, M)
    assert collect_results(ok, ov) == _per_txn(log), (W, records, M)
    fk, fv, _ = plan.fused()(log)
    assert np.array_equal(np.asarray(ok), np.asarray(fk)), (W, records, M)
    assert np.array_equal(np.asarray(ov), np.asarray(fv)), (W, records, M)
print("OK")
"""


def test_sharded_gives_the_group_by_sum_on_four_devices():
    env = {"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    script = _SHARDED.replace("{tests!r}", repr(str(ROOT / "tests")))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]
