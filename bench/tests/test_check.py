"""The comparison, and the control that it has to fail."""

import numpy as np
import pytest

from bench import check
from bench.tests.small import small_cell
from bench.tests.test_apps import ONE_CHIP_CELLS, generate

PAD = check.PAD


def test_wrong_keys_counts_each_fault():
    counts = np.array([2, 0, 1, 3])
    sums = np.array([10, 0, 5, 7])
    good_k = np.array([[0, 2, PAD], [3, PAD, PAD]])
    good_v = np.array([[10, 5, 0], [7, 0, 0]])
    assert check.wrong_keys(good_k, good_v, counts, sums) == 0
    assert check.wrong_keys(good_k, good_v + (good_k == 3), counts,
                            sums) == 1                       # value off
    assert check.wrong_keys(np.where(good_k == 2, PAD, good_k), good_v,
                            counts, sums) == 1               # key missing
    twice = np.array([[0, 2, 3], [3, PAD, PAD]])
    assert check.wrong_keys(twice, np.array([[10, 5, 3], [4, 0, 0]]),
                            counts, sums) >= 1               # emitted twice
    stray = np.array([[0, 2, 9], [3, PAD, PAD]])
    assert check.wrong_keys(stray, np.array([[10, 5, 1], [7, 0, 0]]),
                            counts, sums) == 1               # outside


@pytest.mark.parametrize("name", ONE_CHIP_CELLS)
def test_control_fails_the_comparison(name):
    """The reference with its sums carried in bfloat16 breaks exactness;
    the exact reference, put in the same place, passes."""
    import jax.numpy as jnp

    cell = small_cell(name)
    # Enough pairs per key that a bfloat16 sum (8 bits of mantissa) has to
    # round, as it does at the cells' own sizes (a multiple of 180 tokens).
    cell.config["tokens"] = 180 * 2**12
    tokens = generate(cell, 23)
    keys, vals = cell.app.pairs(np, tokens, cell.config)
    space = cell.config["key_space"]
    counts, sums = check.exact(keys, vals, space)
    assert check.wrong_keys_dense(counts > 0, sums, counts, sums) == 0
    present, ctrl = check.control(jnp.asarray(keys), jnp.asarray(vals), space)
    assert check.wrong_keys_dense(present, ctrl, counts, sums) > 0
