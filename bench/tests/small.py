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


def shrink(cell: harness.Cell) -> harness.Cell:
    cell.config.update(SMALL[cell.config["app"]])
    return cell


def small_cell(name: str) -> harness.Cell:
    return shrink(full_cell(name))


def cell_beside(root, name: str, config: str, traffic: str, mix: dict,
                chips: int) -> harness.Cell:
    """A cell that ``BENCHMARK.json`` does not hold, resolved the way the
    harness resolves every cell: from a copy of the manifest with the
    cell added, under ``root``, where the new traffic file ``mix`` lies
    beside links to the benchmark's configurations and apps."""
    import json
    from pathlib import Path

    root = Path(root)
    bench = root / "bench"
    (bench / "traffic").mkdir(parents=True, exist_ok=True)
    for sub in ("configs", "apps"):
        if not (bench / sub).exists():
            (bench / sub).symlink_to(harness.BENCH / sub)
    (bench / "traffic" / f"{traffic}.json").write_text(json.dumps(mix))
    manifest = harness.read_json(harness.ROOT / "BENCHMARK.json")
    w = {"name": name, "config": config, "traffic": traffic,
         "chips": chips, "why": "a test cell"}
    manifest["workloads"].append(w)
    return harness.cell_from(w, manifest, root)
