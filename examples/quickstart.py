"""Quickstart: the paper's 3-phase pipeline in ~40 lines.

Profile a WordCount MapReduce job under different (#mappers, #reducers)
settings, fit the multivariate cubic regression (Eqn. 6), and predict the
execution time of unseen configurations.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import ModelDatabase, fit, grid, profile_experiments
from repro.mapreduce import JobConfig, build_job, wordcount, wordcount_corpus

enable_compile_cache()

# --- the application (black box to the modeling pipeline) -----------------
corpus = wordcount_corpus(1 << 15, vocab_size=2048, seed=0)
app = wordcount(2048)
_jobs: dict = {}


def run_job(config) -> float:
    """Total execution time (s) of one WordCount run under `config`."""
    import time, jax
    M, R = int(config[0]), int(config[1])
    if (M, R) not in _jobs:
        _jobs[(M, R)] = build_job(
            app, JobConfig(num_mappers=M, num_reducers=R), len(corpus)
        )
        jax.block_until_ready(_jobs[(M, R)](corpus))  # warmup (job setup)
    t0 = time.perf_counter()
    jax.block_until_ready(_jobs[(M, R)](corpus))
    return time.perf_counter() - t0


# --- phase 1: profiling (paper Fig. 2a; 5 repeats, mean) -------------------
configs = grid([(5, 40, 12), (5, 40, 12)])  # 16 experiments
prof = profile_experiments(run_job, configs, repeats=5,
                           param_names=("mappers", "reducers"), verbose=True)

# --- phase 2: modeling (Eqn. 6: A = (P^T P)^-1 P^T T) ----------------------
model = fit(prof.params, prof.times)
print(f"\nfit: train MAPE {model.train_mape:.2f}%  R^2 {model.r2:.3f}")
print("coefficients:", dict(zip(model.spec.column_names(),
                                np.round(model.coef, 6))))

# --- phase 3: prediction (paper Fig. 2b) -----------------------------------
db = ModelDatabase()
db.put("wordcount", "this-host", model)
for m, r in [(10, 10), (24, 7), (37, 30)]:
    pred = db.predict("wordcount", "this-host", [m, r])
    actual = np.mean([run_job((m, r)) for _ in range(3)])
    print(f"M={m:2d} R={r:2d}: predicted {pred * 1e3:7.2f}ms  "
          f"actual {actual * 1e3:7.2f}ms  "
          f"err {abs(pred - actual) / actual * 100:5.1f}%")
