"""The reduction from a trace to metrics, on a small trace recorded on one
TPU v5e chip (``data/v5e_probe.xplane.pb``: three runs of a jitted sort +
segment sum named ``bench_small``, each inside ``bench.submit`` /
``bench.wait`` / ``bench.release`` host spans) and on events built here."""

from pathlib import Path

import pytest

from bench import trace as tr
from bench.harness import Readings

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def probe():
    return tr.load(DATA / "v5e_probe.xplane.pb")


def test_recorded_trace_planes(probe):
    assert probe.devices == [0]
    runs = tr.module_runs(probe, "bench_small")
    assert len(runs) == 3 and all(0.005 < r < 0.02 for r in runs)
    assert {s.name for s in probe.spans} == {
        "bench.submit", "bench.wait", "bench.release"}


def test_recorded_trace_busy_and_ops(probe):
    busy = tr.busy_ns(probe, 0) / 1e9
    modules = sum(tr.module_runs(probe, "bench_small"))
    assert 0.9 * modules < busy <= modules * 1.001
    ops = dict(tr.top_ops(probe, 0))
    assert "sort" in ops and "fusion" in ops
    assert abs(sum(tr.op_seconds(probe, 0).values()) - busy) < 1e-6


def test_recorded_trace_idle_gaps(probe):
    gaps = tr.idle_gaps(probe, 0)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # The host sleeps 10 ms in each release span between runs.
    assert gaps[0][0] == "bench.release" and gaps[0][1] > 0.009


def test_opcode_and_names():
    text = ("%sort.6 = (s32[1048576]{0:T(1024)S(1)}, s32[1048576]{0}) "
            "sort(s32[1048576]{0:T(1024)S(1)} %a, s32[1048576] %b), "
            "dimensions={0}")
    assert tr.opcode(text) == "sort" and tr.op_name(text) == "sort"
    text = ("%all-gather.14 = s32[4,2,3072]{2,1,0:T(2,128)S(1)} "
            "all-gather(%bitcast.169), channel_id=8")
    assert tr.opcode(text) == "all-gather" and tr.op_name(text) == \
        "all-gather"
    text = "%while.3 = (s32[], s32[4]{0}) while((s32[], s32[4]{0}) %t)"
    assert tr.opcode(text) in tr.CONTAINERS
    text = "%all_to_all.19 = s32[4,1,960]{2,1,0} all-to-all(%copy.19)"
    assert tr.opcode(text) == "all-to-all"
    assert tr.op_name("%add_select_fusion.2 = s32[4] fusion(%x.1)") == \
        "add_select_fusion"
    assert tr.module_name("jit_bench_map(2775352640231577865)") == \
        "jit_bench_map"


def test_union_and_readings():
    E = tr.Event
    a2a = "%all_to_all.1 = s32[4,8]{1,0} all-to-all(%x)"
    gather = "%all-gather-start.2 = s32[8]{0} all-gather-start(%y)"
    t = tr.Trace(
        ops={0: [E("%f.1 = s32[] fusion(%a)", 0, 100), E(a2a, 50, 100),
                 E(gather, 400, 50)],
             1: [E(a2a, 0, 300)]},
        modules={0: [E("jit_bench_map(1)", 0, 2e6),
                     E("jit_bench_map(1)", 3e6, 4e6)]},
        spans=[E("bench.submit", 0, 10), E("bench.wait", 10, 600)])
    assert tr.union(t.ops[0]) == [(0, 150), (400, 450)]
    assert tr.busy_ns(t, 0) == 200
    assert tr.idle_gaps(t, 0) == [["bench.wait", 250e-9],
                                  ["bench.wait", 160e-9]]
    r = Readings(window=t, jobs=2, window_s=1e-6, devices=[0, 1])
    assert r.busy_s == pytest.approx(250e-9)
    assert r.scopes == {} and r.counters is None
    assert tr.module_runs(t, "bench_map") == pytest.approx([2e-3, 4e-3])
