"""Cells on several chips: a mode that takes a mesh runs on a 1-D
``workers`` mesh with its input split over the chips, on four virtual CPU
devices; and the harness refuses a multi-chip cell it cannot run."""

import json
import os
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.tests.conftest import ROOT
from bench.tests.small import cell_beside, shrink

CASES = {
    # the paper's Exim job on its 4-node platform, cut to a test's size
    "exim": ("exim-mainlog", {"num_mappers": 20, "num_reducers": 4,
                              "combiner": False, "reduce_backend": "xla"}),
    "wordcount": ("wordcount-hibench", {"num_mappers": 20,
                                        "num_reducers": 5, "combiner": True,
                                        "reduce_backend": "jnp"}),
}


def mix(mode: str, workers: int, **job) -> dict:
    return {"why": "a test mix", "mode": mode,
            "job": {"num_workers": workers, "shuffle_backend": "all_to_all",
                    **job}}


@pytest.mark.parametrize("mode,workers,tokens,match", [
    ("fused", 4, None, "takes no mesh"),
    ("sharded", 1, None, "num_workers=1"),
    ("sharded", 4, 4098, "split evenly"),
])
def test_build_entry_refuses_a_cell_it_cannot_run(tmp_path, mode, workers,
                                                  tokens, match):
    config, job = CASES["wordcount"]
    cell = shrink(cell_beside(tmp_path, "wc.w4", config, "w4",
                             mix(mode, workers, **job), chips=4))
    if tokens:
        cell.config["tokens"] = tokens
    with pytest.raises(ValueError, match=match):
        harness.build_entry(cell, jax.devices())


_FOUR_CHIPS = r"""
import json, sys, time
from pathlib import Path
import jax
from bench import harness
from bench import scopes as sc
from bench import trace as tr
from bench.tests import test_mesh as tm
from bench.tests.small import cell_beside, shrink

root, out = Path(sys.argv[1]), {}
seen = []
real_loop = harness.closed_loop


def spy(job, tokens, *args):
    s = tokens.sharding
    seen.append({"devices": len(s.device_set), "spec": str(s.spec),
                 "shards": sorted({sh.data.shape[0]
                                   for sh in tokens.addressable_shards}),
                 "tokens": tokens.shape[0]})
    return real_loop(job, tokens, *args)


def synthetic_trace(directory):
    # The CPU's trace has no device planes, so the traced window is
    # replaced by one op per instruction of the compiled job, on each of
    # the four devices, in two runs of its module.
    hlo = (Path(directory).parent / "job.hlo.txt").read_text()
    names, module = list(sc.scope_map(hlo)), sc.module_of(hlo)
    E, t = tr.Event, tr.Trace()
    for d in range(4):
        for run in range(2):
            t0 = run * 1e6
            t.modules.setdefault(d, []).append(
                E(f"{module}(1)", t0, 1000 * len(names)))
            t.ops.setdefault(d, []).extend(
                E(f"%{n} = s32[] op()", t0 + 1000 * i, 1000)
                for i, n in enumerate(names))
    return t


harness.closed_loop = spy
harness._load_trace = synthetic_trace
for case, (config, job) in tm.CASES.items():
    cell = shrink(cell_beside(root, f"{case}.w4", config, f"{case}-w4",
                              tm.mix("sharded", 4, **job), chips=4))
    cell.per_layer = harness.read_json(harness.ROOT / "BENCHMARK.json")[
        "per_layer"]
    for trace in (False, True):
        seen.clear()
        r = harness.run(cell, 2**31 + 41, 0.2, trace, jax.devices(),
                        time.perf_counter(),
                        save=str(root / f"{case}-save") if trace else None)
        r.pop("_log")
        out[f"{case}.{int(trace)}"] = {**r, "input": seen[0]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    root = tmp_path_factory.mktemp("mesh")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)])}
    r = subprocess.run([sys.executable, "-c", _FOUR_CHIPS, str(root)],
                       env=env, capture_output=True, text=True, timeout=600,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("trace", [0, 1])
def test_sharded_cell_runs_correct_on_four_devices(four_chips, case, trace):
    r = four_chips[f"{case}.{trace}"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["compared"]["dropped"]["value"] == 0
    assert r["device"]["count"] == 4
    n = r["input"]["tokens"]
    assert r["input"] == {"devices": 4, "spec": "PartitionSpec('workers',)",
                          "shards": [n // 4], "tokens": n}


@pytest.mark.parametrize("case", CASES)
def test_sharded_cell_reads_its_scopes_and_counters(four_chips, case):
    metrics = four_chips[f"{case}.1"]["metrics"]
    combiner = CASES[case][1]["combiner"]
    want = {"map_scope_ms", "shuffle_scope_ms", "reduce_scope_ms",
            "unscoped_ms", "shuffle_live_pct", "reduce_live_pct",
            "device_idle_pct"} | ({"combine_scope_ms"} if combiner else set())
    assert set(metrics) == want
    # what runs after the reduce has no scope of its own: the drop
    # count's sum over the chips and, where R is not W, the gather of the
    # output onto every chip
    assert metrics["unscoped_ms"]["value"] > 0
    for name in ("shuffle_live_pct", "reduce_live_pct"):
        assert 0 < metrics[name]["value"] <= 100
