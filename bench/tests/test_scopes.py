"""Per-phase device time from the job's own scopes, on events built here
and on a trace recorded on one TPU v5e chip (``data/scoped.xplane.pb.gz``:
the WordCount cell's fused job, with its combiner, at 2^16 words, three
runs in the benchmark's closed loop under ``bench.submit`` /
``bench.wait`` / ``bench.release`` host spans, and
``data/scoped.hlo.txt.gz``, the compiled job's ``as_text()`` without its
source locations; recorded by ``python3 bench/scopes.py --workload
wordcount-hibench.m20r5-combine --seed 3000000221 --seconds 0.02 --tokens
65536 --save <dir>``, then gzipped, with the checkout's path in the ops'
source stats replaced by ``<checkout>/``)."""

import gzip
from pathlib import Path

import pytest

from bench import scopes as sc
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"

HLO = """\
HloModule jit_job, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%cmp (a: s32[], b: s32[]) -> pred[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT, metadata={op_name="lt"}
}

%fused_computation (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %neg.1 = s32[8]{0} negate(%p)
}

%body (t: (s32[], s32[8])) -> (s32[], s32[8]) {
  %t = (s32[], s32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = s32[8]{0} get-tuple-element(%t), index=1
  %copy.3 = s32[8]{0} copy(%x)
  %add.2 = s32[8]{0} add(%copy.3, %copy.3), metadata={op_name="jit(job)/mr.reduce/while/body/add"}
  ROOT %r = (s32[], s32[8]{0}) tuple(%i, %add.2)
}

%cond (t: (s32[], s32[8])) -> pred[] {
  %t.1 = (s32[], s32[8]{0}) parameter(0)
  ROOT %c = pred[] constant(false)
}

ENTRY %main.9 (x.1: s32[8]) -> s32[8] {
  %x.1 = s32[8]{0} parameter(0)
  %neg_fusion = s32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(job)/mr.map/neg"}
  %sort.6 = s32[8]{0} sort(%neg_fusion), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(job)/mr.shuffle/sort"}
  %copy.7 = s32[8]{0} copy(%sort.6)
  %tup = (s32[], s32[8]{0}) tuple(%x.1, %copy.7)
  %while.1 = (s32[], s32[8]{0}) while(%tup), condition=%cond, body=%body, metadata={op_name="jit(job)/mr.reduce/while"}
  %gte = s32[8]{0} get-tuple-element(%while.1), index=1
  ROOT %sum = s32[8]{0} add(%gte, %gte), metadata={op_name="jit(job)/reduce_sum"}
}
"""


def test_scope_map_own_callers_and_compiler_made():
    s = sc.scope_map(HLO)
    # own scopes
    assert s["neg_fusion"] == "map" and s["sort.6"] == "shuffle"
    assert s["while.1"] == s["add.2"] == "reduce"
    # called computations take the caller's phase: a fusion's body, a
    # sort's comparator (its own op_name is no program path), a loop body
    assert s["neg.1"] == "map" and s["lt"] == "shuffle"
    assert s["copy.3"] == s["i"] == "reduce" and s["c"] == "reduce"
    # a compiler-made instruction without metadata takes its operand's
    assert s["copy.7"] == "shuffle"
    # a program op outside every scope stays unscoped
    assert s["sum"] == sc.UNSCOPED
    assert sc.module_of(HLO) == "jit_job"
    assert sc.instruction("%sort.6 = s32[8]{0} sort(%neg_fusion)") == \
        "sort.6"


def test_scope_map_without_scopes_is_unscoped():
    bare = HLO.replace("mr.", "phase_")
    assert set(sc.scope_map(bare).values()) == {sc.UNSCOPED}


def test_scope_ms_counts_only_the_jobs_own_runs():
    E = tr.Event
    scopes = {"f.1": "map", "s.2": "shuffle", "r.3": "reduce"}
    t = tr.Trace(
        ops={0: [
            # job run 1: 0..1000
            E("%f.1 = s32[8] fusion(%x)", 0, 400),
            E("%s.2 = s32[8] sort(%f.1)", 300, 300),   # overlaps map
            E("%r.3 = s32[8] add(%s.2)", 600, 200),
            E("%c.9 = s32[8] copy(%r.3)", 800, 100),   # not in the map
            # the fingerprint program between runs: same names, other
            # module, left out
            E("%f.1 = u32[] fusion(%y)", 1100, 500),
            # job run 2: 2000..3000
            E("%f.1 = s32[8] fusion(%x)", 2000, 200),
            E("%r.3 = s32[8] add(%s.2)", 2500, 400),
        ]},
        modules={0: [E("jit_job(7)", 0, 1000),
                     E("jit_fingerprint(3)", 1050, 600),
                     E("jit_job(7)", 2000, 1000)]})
    ms = sc.scope_ms(t, 0, "jit_job", scopes)
    assert ms == pytest.approx({"map": 300e-6, "shuffle": 150e-6,
                                "reduce": 300e-6, "unscoped": 50e-6})
    # busy is the union over all of the job's ops: 0..900 and 2000..2200
    # and 2500..2900 over two runs
    assert sc.busy_ms(t, 0, "jit_job") == pytest.approx(750e-6)
    assert sc.scope_ms(t, 0, "jit_other", scopes) == {}
    assert sc.busy_ms(t, 0, "jit_other") is None
    assert sc.scope_ms(t, 1, "jit_job", scopes) == {}


def test_live_pct_from_counters():
    c = {"shuffle.slots": 88_473_600, "shuffle.pairs": 29_491_200,
         "reduce.slots": 5 * 70_778_880, "reduce.pairs": 29_491_200}
    got = sc.live_pct(c)
    assert got["shuffle_live_pct"] == pytest.approx(100 / 3)
    assert got["reduce_live_pct"] == pytest.approx(100 / 12)
    assert sc.live_pct({"shuffle.slots": 0, "shuffle.pairs": 0}) == {}


def test_without_sources_keeps_what_scope_map_reads():
    text = ('HloModule jit_job\n\nFileNames\n1 "a.py"\nFunctionNames\n'
            '1 "f"\n\nENTRY %main (x: s32[2]) -> s32[2] {\n'
            '  %x = s32[2]{0} parameter(0)\n'
            '  ROOT %n = s32[2]{0} negate(%x), metadata={op_name='
            '"jit(job)/mr.map/neg" source_file="a.py" source_line=3 '
            'stack_frame_id=1}\n}\n')
    stripped = sc.without_sources(text)
    assert "a.py" not in stripped and "FileNames" not in stripped
    assert "stack_frame_id" not in stripped
    assert sc.scope_map(stripped) == sc.scope_map(text) == {
        "x": "map", "n": "map"}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("scoped") / "scoped.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "scoped.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((DATA / "scoped.hlo.txt.gz").read_bytes())
    return tr.load(path), hlo.decode()


def test_recorded_scoped_job(recorded):
    trace, hlo = recorded
    scopes = sc.scope_map(hlo)
    module = sc.module_of(hlo)
    assert module == "jit_job" and trace.devices == [0]
    assert set(scopes.values()) == {"map", "combine", "shuffle", "reduce"}
    runs = tr.module_runs(trace, "job")
    assert len(runs) == 3
    ms = sc.scope_ms(trace, 0, module, scopes)
    busy = sc.busy_ms(trace, 0, module)
    # every op of the job is in a phase: nothing unscoped
    assert set(ms) == {"map", "combine", "shuffle", "reduce"}
    assert all(v > 0 for v in ms.values())
    # the phases cover the job's busy time once, and overlap only where
    # async ops cross a phase boundary (2.2% of this small job's busy
    # time as recorded; the union of all phases is the busy time)
    assert busy <= sum(ms.values()) <= 1.05 * busy
    assert busy <= 1e3 * sum(runs) / len(runs)
    # the fingerprint between jobs ran, and is not counted
    assert len(tr.module_runs(trace, "fingerprint")) == 3
    all_ops = sum(b - a for a, b in tr.union(trace.ops[0])) / 1e6
    assert all_ops > busy * len(runs)
