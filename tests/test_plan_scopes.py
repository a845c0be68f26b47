"""Phase scopes and device counters: every mode names its phases in the
compiled program, and counts the pairs and slots at each phase boundary
on the device.

The scopes are metadata only (the program runs the same operations), and
the counters are exact: they equal numpy's counts of the buffers the
phases hand each other.  ``bench.scopes.scope_map`` is the reader the
benchmark uses to attribute a compiled job's instructions to phases.
"""

import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.mapreduce import (
    ExecutionPlan,
    JobConfig,
    eximparse,
    phases,
    uservisits,
    wordcount,
    wordcount_corpus,
)
from repro.mapreduce.phases import PAD_KEY
from repro.telemetry import PhaseRecorder

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import scopes as sc  # noqa: E402
from bench import trace as tr  # noqa: E402

CORPUS = wordcount_corpus(360, vocab_size=53, seed=9)

#: opcodes that do no work of their own; left out of the coverage count
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element",
            "bitcast")
PHASES = {"map", "combine", "shuffle", "reduce"}


def _exim_log(n_msg: int = 40, seed: int = 5) -> np.ndarray:
    """``[txn, event, size]`` records, three lines per message."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(1024)[:n_msg]
    rec = np.stack([np.repeat(ids, 3), np.tile([0, 1, 2], n_msg),
                    np.repeat(rng.integers(200, 4000, n_msg), 3)], axis=1)
    return rec.astype(np.int32).reshape(-1)


def _uservisits_table(rows: int = 90, seed: int = 7) -> np.ndarray:
    """Rows of 32 random tokens, sourceIP (token 0) below 64, adRevenue
    (token 14) in cents."""
    rng = np.random.default_rng(seed)
    t = rng.integers(0, 2**31, size=(rows, 32))
    t[:, 0] = rng.integers(0, 64, rows)
    t[:, 14] = rng.integers(1, 100_001, rows)
    return t.astype(np.int32).reshape(-1)


APPS = {
    "wordcount": (wordcount(53), CORPUS),
    "exim": (eximparse(1024), _exim_log()),
    # 213 messages: 5 splits of 128 records, whose tokens fill whole
    # vector registers (the lane-tiled split)
    "exim-lane": (eximparse(1024), _exim_log(213)),
    "uservisits": (uservisits(64, 32, 0, 14), _uservisits_table()),
}


def _plan(app_name, **kw):
    app, corpus = APPS[app_name]
    kw.setdefault("num_mappers", 5)
    kw.setdefault("num_reducers", 3)
    kw.setdefault("num_workers", 1)
    kw.setdefault("capacity_factor", 8.0)
    return ExecutionPlan(app, JobConfig(**kw), len(corpus)), corpus


def _instructions(hlo_text: str) -> dict[str, str]:
    """Instruction name -> opcode, over every computation."""
    out = {}
    for line in hlo_text.splitlines():
        m = sc._INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = tr.opcode(line.strip().removeprefix("ROOT "))
    return out


def _job(plan, mode, **kw):
    if mode == "sharded":
        return plan.sharded(jax.make_mesh((1,), ("workers",)), **kw)
    return plan.fused(**kw) if mode == "fused" else plan.pipelined(
        depth=2, **kw)


#: the module each mode's job compiles to
MODULES = {"fused": "jit_job", "pipelined": "jit_job",
           "sharded": "jit_plain"}


@pytest.mark.parametrize("mode", ["fused", "pipelined", "sharded"])
@pytest.mark.parametrize("combiner", [False, True])
@pytest.mark.parametrize("app_name", ["wordcount", "exim", "uservisits",
                                      "exim-lane"])
def test_scope_map_covers_the_compiled_job(app_name, combiner, mode):
    # M=5 over W=1 keeps pipelined(depth=2)'s last wave group ragged.
    plan, corpus = _plan(app_name, combiner=combiner)
    text = _job(plan, mode).lower(corpus).compile().as_text()
    scopes = sc.scope_map(text)
    ops = _instructions(text)
    assert set(scopes) == set(ops)
    work = [n for n, op in ops.items() if op not in PLUMBING]
    scoped = [n for n in work if scopes[n] != sc.UNSCOPED]
    assert len(scoped) >= 0.95 * len(work), (len(scoped), len(work))
    want = PHASES if combiner else PHASES - {"combine"}
    assert set(scopes.values()) - {sc.UNSCOPED} == want
    assert sc.module_of(text) == MODULES[mode]


def test_scopes_are_metadata_only(monkeypatch):
    """Without the scopes the jobs lower to the same operations."""
    plan, corpus = _plan("wordcount", combiner=True)
    scoped = [_job(plan, m).lower(corpus).as_text()
              for m in ("fused", "pipelined")]
    assert "mr.map" in _job(plan, "fused").lower(corpus).as_text(
        debug_info=True)
    monkeypatch.setattr(phases, "scoped", lambda phase, fn: fn)
    bare, _ = _plan("wordcount", combiner=True)
    plain = [_job(bare, m).lower(corpus).as_text()
             for m in ("fused", "pipelined")]
    assert scoped == plain
    # and the reader then finds no phase: its coverage is the scopes'
    text = _job(bare, "fused").lower(corpus).compile().as_text()
    assert set(sc.scope_map(text).values()) == {sc.UNSCOPED}


def _numpy_counts(plan, corpus) -> dict:
    """The counters, counted by numpy on each phase's buffers."""
    fns = {p: jax.jit(f) for p, f in plan.phase_fns().items()}
    bk, bv, bp = fns["map"](corpus)
    _, valid = jax.jit(plan.prep())(corpus)
    want = {"map.pairs": int(np.asarray(bp).sum()),
            "map.slots": np.asarray(bp).size,
            "map.records": int(np.asarray(valid).sum())}
    if "combine" in fns:
        bk, bv, bp = fns["combine"](bk, bv, bp)
        want["combine.pairs"] = int(np.asarray(bp).sum())
    pk, pv, dropped = fns["shuffle"](bk, bv, bp)
    ok, _ = fns["reduce"](pk, pv)
    pk, ok = np.asarray(pk), np.asarray(ok)
    want.update({
        "shuffle.slots": np.asarray(bk).size,
        "shuffle.pairs": int(np.asarray(bp).sum()),
        "shuffle.dropped": int(dropped),
        "reduce.slots": pk.size,
        "reduce.pairs": int((pk != int(PAD_KEY)).sum()),
        "reduce.segments": int((ok != int(PAD_KEY)).sum()),
    })
    return want


CASES = {
    "wordcount": dict(app_name="wordcount"),
    "wordcount-combine": dict(app_name="wordcount", combiner=True),
    "exim": dict(app_name="exim"),
    "exim-a2a-combine": dict(app_name="exim", combiner=True, num_workers=2,
                             shuffle_backend="all_to_all"),
    "exim-lane": dict(app_name="exim-lane"),
    "uservisits": dict(app_name="uservisits"),
    "overflow": dict(app_name="wordcount", num_reducers=4,
                     capacity_factor=1.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_counters_are_exact_and_leave_the_output_alone(case):
    kw = dict(CASES[case])
    plan, corpus = _plan(kw.pop("app_name"), **kw)
    if case == "overflow":
        corpus = np.zeros_like(corpus)
    want = _numpy_counts(plan, corpus)
    if case == "overflow":
        assert want["shuffle.dropped"] > 0
    for mode in ("fused", "pipelined"):
        ok, ov, dropped = _job(plan, mode)(corpus)
        ok_c, ov_c, dropped_c, stats = _job(plan, mode, counters=True)(
            corpus)
        assert np.array_equal(np.asarray(ok), np.asarray(ok_c)), mode
        assert np.array_equal(np.asarray(ov), np.asarray(ov_c)), mode
        assert int(dropped) == int(dropped_c), mode
        assert all(v.dtype == np.int32 and v.shape == ()
                   for v in stats.values()), mode
        assert {k: int(v) for k, v in stats.items()} == want, mode
        assert want["map.slots"] == plan.M * plan.P
        assert want["map.records"] == plan.rows


@pytest.mark.parametrize("combiner", [False, True])
def test_traced_records_the_device_counters(combiner):
    plan, corpus = _plan("wordcount", combiner=combiner, overlap_depth=2)
    *_, stats = plan.fused(counters=True)(corpus)
    c = {k: int(v) for k, v in stats.items()}
    recorder = PhaseRecorder()
    plan.traced(recorder)(corpus)
    trace = recorder.last
    assert trace.counter("map", "pairs_emitted") == c["map.pairs"]
    assert trace.counter("map", "records_in") == c["map.records"]
    assert trace.counter("map", "pairs_capacity") == c["map.slots"]
    if combiner:
        assert trace.counter("combine", "pairs_out") == c["combine.pairs"]
    assert trace.counter("shuffle", "pairs_in") == c["shuffle.pairs"]
    assert trace.counter("shuffle", "pairs_out") == c["reduce.pairs"]
    assert trace.counter("shuffle", "pairs_dropped") == c["shuffle.dropped"]
    assert trace.counter("reduce", "segments_out") == c["reduce.segments"]
    assert trace.counter("reduce", "segment_slots") == c["reduce.slots"]
    pipeline = [p for p in trace.phases if p.phase == "pipeline"]
    assert pipeline and "overlap_s" not in pipeline[0].counters
    assert pipeline[0].counters["overlap_depth"] == 2
    assert trace.check_conservation() == []


def test_traced_phases_are_host_spans_on_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData

    plan, corpus = _plan("wordcount", combiner=True)
    job = plan.traced(PhaseRecorder())
    job(corpus)  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        job(corpus)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert {f"mr.{p}" for p in PHASES} <= names


@pytest.mark.parametrize("combiner", [False, True])
@pytest.mark.parametrize("app_name", ["wordcount", "exim", "exim-lane",
                                      "uservisits"])
def test_sharded_counts_what_the_emulated_collective_counts(app_name,
                                                            combiner):
    """One worker on a mesh of one: the sharded job's summed device
    counters equal the fused job's over the same collective layout, in
    the fused mesh program and in the fenced one."""
    mesh = jax.make_mesh((1,), ("workers",))
    plan, corpus = _plan(app_name, combiner=combiner,
                         shuffle_backend="all_to_all")
    *_, stats = plan.fused(counters=True)(corpus)
    want = {k: int(v) for k, v in stats.items()}
    ok, ov, dropped, got = plan.sharded(mesh, counters=True)(corpus)
    assert {k: int(got[k]) for k in want} == want
    assert got["dropped_send"] + got["dropped_recv"] == int(dropped)
    ok_p, ov_p, _ = plan.sharded(mesh)(corpus)
    assert np.array_equal(np.asarray(ok), np.asarray(ok_p))
    assert np.array_equal(np.asarray(ov), np.asarray(ov_p))
    recorder = PhaseRecorder()
    plan.sharded(mesh, recorder=recorder)(corpus)
    trace = recorder.last
    assert trace.counter("map", "pairs_emitted") == want["map.pairs"]
    assert trace.counter("map", "records_in") == want["map.records"]
    assert trace.counter("map", "pairs_capacity") == want["map.slots"]
    assert trace.counter("shuffle", "pairs_out") == want["reduce.pairs"]
    assert trace.counter("reduce", "segments_out") == \
        want["reduce.segments"]
    assert trace.counter("reduce", "segment_slots") == want["reduce.slots"]
    text = plan.sharded(mesh).lower(corpus).compile().as_text()
    assert set(sc.scope_map(text).values()) >= (
        PHASES if combiner else PHASES - {"combine"})


_FOUR_WORKERS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.mapreduce import ExecutionPlan, JobConfig, wordcount
from repro.mapreduce import wordcount_corpus
from repro.telemetry import PhaseRecorder

mesh = jax.make_mesh((4,), ("workers",))
corpus = wordcount_corpus(5000, vocab_size=129, seed=11)
for combiner in (False, True):
    cfg = JobConfig(num_mappers=6, num_reducers=5, num_workers=4,
                    capacity_factor=12.0, combiner=combiner,
                    shuffle_backend="all_to_all")
    plan = ExecutionPlan(wordcount(129), cfg, len(corpus))
    *_, stats = plan.fused(counters=True)(corpus)
    want = {k: int(v) for k, v in stats.items()}
    *_, got = plan.sharded(mesh, counters=True)(corpus)
    got = {k: int(got[k]) for k in want}
    # the mesh walks whole waves: 2 x 4 task rows and 2 x 4 reducer rows
    assert got.pop("map.slots") == 8 * plan.P
    assert got.pop("shuffle.slots") == 8 * plan.shuffle_width
    assert got.pop("reduce.slots") == 8 * plan.partition_cap(4)
    assert got == {k: v for k, v in want.items() if "slots" not in k}
    rec = PhaseRecorder()
    plan.sharded(mesh, recorder=rec)(corpus)
    t = rec.last
    assert t.counter("map", "pairs_emitted") == want["map.pairs"]
    assert t.counter("map", "records_in") == want["map.records"]
    assert t.counter("map", "pairs_capacity") == 8 * plan.P
    assert t.counter("shuffle", "pairs_in") == want["shuffle.pairs"]
    assert t.counter("shuffle", "pairs_out") == want["reduce.pairs"]
    assert t.counter("reduce", "segments_out") == want["reduce.segments"]
    assert t.check_conservation() == []
print("OK")
"""


@pytest.mark.slow
def test_sharded_counters_on_four_workers():
    env = {"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    r = subprocess.run([sys.executable, "-c", _FOUR_WORKERS], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


def test_map_live_pct_reads_the_map_counters():
    from types import SimpleNamespace

    from bench import harness

    reader = harness.load_module(harness.BENCH / "metrics" /
                                 "map_live_pct.py")
    # one pair slot per Exim record, and 5 splits of 24 records: all live
    plan, corpus = _plan("exim")
    *_, stats = plan.fused(counters=True)(corpus)
    counters = {k: int(v) for k, v in stats.items()}
    assert reader.read(SimpleNamespace(counters=counters)) == 100.0
    assert reader.read(SimpleNamespace(counters={
        "map.pairs": 5, "map.slots": 20})) == 25.0
    # a program that counts no map slots, and a run without counters
    assert reader.read(SimpleNamespace(counters={"map.pairs": 5})) is None
    assert reader.read(SimpleNamespace(counters=None)) is None
