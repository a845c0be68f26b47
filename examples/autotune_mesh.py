"""Beyond-paper closure: the paper's config->time model autotunes the MESH.

The paper's configuration parameters are (#mappers, #reducers); the exact
analogue for a distributed JAX workload is the mesh factorization
(data_parallel x model_parallel).  This example:

1. enumerates (data, model) factorizations of a 32-chip slice;
2. "profiles" a llama-style train step under a SAMPLE of them using the
   analytic roofline timer from the compiled dry-run (this container has no
   TPU — on real hardware, swap in `core.profiler.timeit`);
3. fits the paper's regression on log2(data_axis) as the parameter;
4. predicts the best factorization and validates against the exhaustive
   sweep.

    PYTHONPATH=src python examples/autotune_mesh.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=32")

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402

import repro.configs as C  # noqa: E402
from repro.core import fit, mesh_factorizations  # noqa: E402
from repro.launch import cells  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def analytic_step_time(arch_cfg, shape_name, data_ax, model_ax) -> float:
    mesh = make_mesh((data_ax, model_ax), ("data", "model"))
    r = cells.analyze_cell_extrapolated(
        arch_cfg.name, shape_name, mesh, cfg=arch_cfg
    )
    roof = r["roofline"]
    return roof["step_time_no_overlap"]


def main() -> None:
    # scaled-down llama so 32 host devices + CPU compiles stay snappy
    cfg = dataclasses.replace(
        C.smoke_config("llama3-8b"),
        name="llama3-8b", d_model=256, n_heads=8, n_kv_heads=4, head_dim=32,
        d_ff=1024, n_layers=4, vocab_size=8192, param_dtype="bfloat16",
    )
    shape_name = "train_4k"
    C.SHAPES[shape_name] = dataclasses.replace(
        C.SHAPES[shape_name], seq_len=512, global_batch=32
    )
    space = mesh_factorizations(32, min_axis=1)  # (1,32) ... (32,1)
    print(f"config space: {[tuple(map(int, r)) for r in space]}")

    # profile a sample (every other factorization)
    sample = space[::2]
    times = []
    for d, m in sample:
        t = analytic_step_time(cfg, shape_name, int(d), int(m))
        times.append(t)
        print(f"profiled data={int(d):2d} model={int(m):2d}: "
              f"{t * 1e3:8.2f}ms (analytic)")
    # model on log2(data) — the natural smooth parameterization
    x = np.log2(sample[:, :1])
    model = fit(x, np.asarray(times), degree=3, scale=True, lam=1e-9)
    pred = np.asarray(model.predict(np.log2(space[:, :1])))
    best = int(np.argmin(pred))
    print(f"\npredicted best: data={int(space[best][0])} "
          f"model={int(space[best][1])} "
          f"({float(pred[best]) * 1e3:.2f}ms predicted)")

    # validate against exhaustive
    full = [analytic_step_time(cfg, shape_name, int(d), int(m))
            for d, m in space]
    true_best = int(np.argmin(full))
    chosen_time = full[best]
    regret = (chosen_time - full[true_best]) / full[true_best] * 100
    print(f"exhaustive best: data={int(space[true_best][0])} "
          f"model={int(space[true_best][1])} "
          f"({full[true_best] * 1e3:.2f}ms)")
    print(f"tuner regret: {regret:.2f}% using {len(sample)}/{len(space)} "
          f"profiles")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
