"""Cells cut to sizes a CPU test can hold."""

from bench import harness

#: per app: the sizes that replace the configuration's, keeping every
#: other key (Exim: a multiple of 3 * M * 3 = 180 tokens)
SMALL = {
    "exim": {"tokens": 180 * 24, "key_space": 4096},
    "wordcount": {"tokens": 4096},
}


def full_cell(name: str) -> harness.Cell:
    return harness.resolve(name)


def small_cell(name: str) -> harness.Cell:
    cell = full_cell(name)
    cell.config.update(SMALL[cell.config["app"]])
    return cell
