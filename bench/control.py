"""The readings that the limits of ``bench/check.py`` are set from.

    python3 bench/control.py --workload exim-mainlog.m20r5 --seeds 1 2 3

For each seed, in one process: make the cell's input, run the cell's
timed entry once (the program the window drives, at the timed size) and
compare its whole output with the reference: the lower reading.  Then put
the control in the program's place, the reference aggregated in bfloat16
on the device, and compare it the same way: the upper reading.  One line
per seed, then one JSON line.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import check, harness

    cell = harness.resolve(args.workload)
    harness.enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    used = devices[: cell.chips]
    _, job, sharding = harness.build_entry(cell, used)
    gen = jax.jit(lambda k: cell.app.generate(cell.config, k),
                  out_shardings=sharding)
    compiled = None
    space = cell.config["key_space"]
    readings = {"program": {}, "control": {}}
    for seed in args.seeds:
        t = time.perf_counter()
        tokens = jax.block_until_ready(gen(harness.seed_key(seed)))
        if compiled is None:
            compiled = job.lower(tokens).compile()
        ok, ov, dropped = jax.block_until_ready(compiled(tokens))
        out_keys, out_vals, dropped = (np.asarray(ok), np.asarray(ov),
                                       int(dropped))
        del ok, ov
        host = np.asarray(tokens)
        keys, vals = cell.app.pairs(np, host, cell.config)
        counts, sums = check.exact(keys, vals, space)
        wrong = check.wrong_keys(out_keys, out_vals, counts, sums)
        del out_keys, out_vals
        dev_keys, dev_vals = cell.app.pairs(
            jax.numpy, jax.device_put(host, used[0]), cell.config)
        present, ctrl = check.control(dev_keys, dev_vals, space)
        ctrl_wrong = check.wrong_keys_dense(present, ctrl, counts, sums)
        del tokens, dev_keys, dev_vals
        readings["program"][seed] = {"wrong_keys": wrong, "dropped": dropped}
        readings["control"][seed] = {"wrong_keys": ctrl_wrong}
        print(f"seed={seed} program_wrong_keys={wrong} dropped={dropped} "
              f"control_wrong_keys={ctrl_wrong} of {int((counts > 0).sum())}"
              f" keys, {time.perf_counter() - t:.1f} s", flush=True)
    prog = readings["program"].values()
    summary = {
        "workload": cell.name,
        "lower": {k: max(r[k] for r in prog)
                  for k in ("wrong_keys", "dropped")},
        "upper": {"wrong_keys": min(r["wrong_keys"]
                                    for r in readings["control"].values())},
        "readings": readings,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
