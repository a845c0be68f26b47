"""The generators, the Exim log's record alignment, and the references
against the program at a small size."""

import numpy as np
import pytest

from bench import check, harness
from bench.tests.small import full_cell, small_cell

ONE_CHIP_CELLS = ["exim-mainlog.m20r5", "wordcount-hibench.m20r5-combine"]


def generate(cell, seed):
    import jax

    return np.asarray(jax.jit(
        lambda k: cell.app.generate(cell.config, k))(harness.seed_key(seed)))


@pytest.mark.parametrize("name", ONE_CHIP_CELLS)
def test_generator_is_deterministic_per_seed(name):
    cell = small_cell(name)
    a, b = generate(cell, 2**31 + 17), generate(cell, 2**31 + 17)
    c = generate(cell, 2**31 + 18)
    assert a.dtype == np.int32 and a.shape == (cell.config["tokens"],)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_seeds_past_32_bits_differ():
    cell = small_cell("wordcount-hibench.m20r5-combine")
    assert not np.array_equal(generate(cell, 7), generate(cell, 7 + 2**32))


def test_exim_splits_start_on_records():
    cell = full_cell("exim-mainlog.m20r5")
    job = cell.traffic["job"]
    assert cell.config["tokens"] % (3 * job["num_mappers"]) == 0
    cell.app.validate(cell.config, job)
    with pytest.raises(ValueError, match="inside a record"):
        cell.app.validate(dict(cell.config, tokens=cell.config["tokens"] + 9),
                          job)
    with pytest.raises(ValueError, match="inside a message"):
        cell.app.validate(dict(cell.config, tokens=cell.config["tokens"] + 60),
                          job)


def test_exim_log_records():
    cell = small_cell("exim-mainlog.m20r5")
    log = generate(cell, 5).reshape(-1, 3)
    txn, event, size = log.T
    lines = cell.config["lines_per_message"]
    lo, hi = cell.config["size_bytes"]
    msg = log.reshape(-1, lines, 3)
    # Each message: arrival, deliveries, completion, under one id of its
    # own, its size on every line but the completion.
    assert (msg[:, 0, 1] == 0).all() and (msg[:, -1, 1] == 2).all()
    assert (msg[:, 1:-1, 1] == 1).all()
    assert (msg[:, :, 0] == msg[:, :1, 0]).all()
    assert len(np.unique(msg[:, 0, 0])) == len(msg)
    assert ((txn >= 0) & (txn < cell.config["key_space"])).all()
    assert (msg[:, -1, 2] == 0).all()
    sizes = msg[:, :-1, 2]
    assert ((sizes >= lo) & (sizes <= hi)).all()
    assert (sizes == sizes[:, :1]).all()


def test_wordcount_words_cover_the_vocabulary():
    cell = small_cell("wordcount-hibench.m20r5-combine")
    words = generate(cell, 9)
    counts = np.bincount(words, minlength=cell.config["key_space"])
    assert counts.shape == (cell.config["key_space"],)
    # Uniform: 4096 draws over 1000 words, every count near 4.
    assert (counts > 0).mean() > 0.95 and counts.max() < 20


@pytest.mark.parametrize("name", ONE_CHIP_CELLS)
def test_reference_equals_program(name):
    import jax

    cell = small_cell(name)
    _, job, _ = harness.build_entry(cell, jax.devices())
    tokens = generate(cell, 11)
    ok, ov, dropped = job(tokens)
    counts, sums = check.exact(*cell.app.pairs(np, tokens, cell.config),
                               cell.config["key_space"])
    assert int(dropped) == 0
    assert check.wrong_keys(ok, ov, counts, sums) == 0
