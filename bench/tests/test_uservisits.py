"""The UserVisits aggregation cell at a small size: its generator, its
reference against the program, and whole harness runs, sound and broken."""

import numpy as np
import pytest

from bench import check, harness
from bench.tests.small import full_cell
from bench.tests.test_apps import generate
from bench.tests.test_faults import (_alter_one_answer, _drops_a_pair,
                                     _half_input_left_out, _run,
                                     _state_unchanged)

CELL = "uservisits-aggregation.m20r5"
#: 4,000 rows: splits of 200 rows fill whole vector registers, as at the
#: cell's own size
ROWS = 4000


def small(rows: int = ROWS) -> harness.Cell:
    cell = full_cell(CELL)
    width = cell.app.layout(cell.config)[0]
    cell.config.update(rows=rows, tokens=rows * width, key_space=512)
    return cell


def test_the_cell_is_the_deployment():
    cell = full_cell(CELL)
    width, ip_col, revenue_col = cell.app.layout(cell.config)
    assert (width, ip_col, revenue_col) == (32, 0, 14)
    assert len(cell.config["columns"]) == 9
    assert cell.config["tokens"] == cell.config["rows"] * width
    assert cell.config["key_space"] == 2**22
    assert cell.traffic["job"]["combiner"] is False
    cell.app.validate(cell.config, cell.traffic["job"])
    with pytest.raises(ValueError, match="not rows"):
        cell.app.validate(dict(cell.config, tokens=cell.config["tokens"] + 1),
                          cell.traffic["job"])


def test_generator_is_deterministic_per_seed_and_in_range():
    cell = small()
    a, b = generate(cell, 2**31 + 17), generate(cell, 2**31 + 17)
    c = generate(cell, 2**31 + 18)
    assert a.dtype == np.int32 and a.shape == (cell.config["tokens"],)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert not np.array_equal(generate(cell, 7), generate(cell, 7 + 2**32))
    rows = a.reshape(ROWS, 32)
    ip, revenue = rows[:, 0], rows[:, 14]
    assert ((ip >= 0) & (ip < 512)).all() and len(np.unique(ip)) > 400
    lo, hi = cell.config["revenue_cents"]
    assert ((revenue >= lo) & (revenue <= hi)).all()
    assert revenue.std() > (hi - lo) / 4
    # every other column is random content, never a constant
    rest = np.delete(rows, [0, 14], axis=1)
    assert all(len(np.unique(rest[:, j])) > ROWS // 2
               for j in range(rest.shape[1]))


@pytest.mark.parametrize("rows", [ROWS, 2003])
def test_reference_equals_program(rows):
    import jax

    cell = small(rows)
    _, job, _ = harness.build_entry(cell, jax.devices())
    tokens = generate(cell, 11)
    ok, ov, dropped = job(tokens)
    keys, values = cell.app.pairs(np, tokens, cell.config)
    assert keys.shape == values.shape == (rows,)
    counts, sums = check.exact(keys, values, cell.config["key_space"])
    assert int(dropped) == 0
    assert check.wrong_keys(ok, ov, counts, sums) == 0


def test_sound_run_is_correct():
    r = _run(small())
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"job_s", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize("fault", [_alter_one_answer, _half_input_left_out,
                                   _state_unchanged, _drops_a_pair])
def test_broken_run_is_not_correct(fault, monkeypatch):
    r = _run(small(), monkeypatch, fault)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]
