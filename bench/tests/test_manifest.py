"""``BENCHMARK.json`` resolves to its files and keeps the naming rules."""

import json
import re

import pytest

from bench import harness
from bench.tests.conftest import ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = harness.resolve(cell)
    assert c.chips in (1, 4)
    assert c.end_to_end and c.per_layer
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    for m in c.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert hasattr(harness.load_module(
            ROOT / "bench" / "metrics" / f"{m['name']}.py"), "read")
    for key in ("validate", "make_app", "generate", "pairs"):
        assert callable(getattr(c.app, key))


def test_names_and_units():
    names = [c["name"] for c in MANIFEST["configs"]] + CELLS
    names += [m["name"] for m in MANIFEST["end_to_end"]
              + MANIFEST["per_layer"]]
    names += [w[k] for w in MANIFEST["workloads"] for k in ("config",
                                                             "traffic")]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in MANIFEST[group]]
        assert len(group_names) == len(set(group_names)), group


def test_text_fields_fit():
    texts = [e["why"] for e in MANIFEST["configs"] + MANIFEST["workloads"]]
    texts += [c["source"] for c in MANIFEST["configs"]]
    texts += [m["layer"] for m in MANIFEST["per_layer"]]
    texts += MANIFEST["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t


def test_configs_hold_their_reductions():
    for c in MANIFEST["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert config["guarantee"]


def test_per_layer_metrics_name_known_cells_and_moves():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_metric_sources_are_allowed():
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace"), m
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock"), m


@pytest.mark.parametrize("cell", CELLS)
def test_multi_chip_cells_run_on_a_mesh(cell):
    """A cell on several chips runs a mode that takes a mesh, with one
    worker per chip and its input split evenly over them."""
    from repro.mapreduce import ExecutionPlan

    c = harness.resolve(cell)
    if c.chips == 1:
        return
    assert harness.takes_mesh(getattr(ExecutionPlan, c.traffic["mode"]))
    assert c.traffic["job"]["num_workers"] == c.chips
    assert c.config["tokens"] % c.chips == 0
