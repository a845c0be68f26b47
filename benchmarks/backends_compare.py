"""Per-backend timing comparison (beyond-paper: the execution strategy as a
configuration axis).

For WordCount and Exim parse, times every registered reduce backend on a
small (M, R) grid, verifies all backends agree with the ``jnp`` reference
output, and reports the measured-best backend per application.

CSV rows:
  backends,<app>,<backend>,<M>,<R>,<mean_s>
  backends,<app>,equivalence,ok,,
  backends,<app>,best,<backend>,,
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import JobRunner, make_app
from repro.core.profiler import profile_categorical
from repro.mapreduce import (
    JobConfig,
    REDUCE_BACKENDS,
    build_job,
    collect_results,
)

# Partition capacity grows ~ tokens/R; the Pallas kernel builds a (C, C)
# one-hot per partition and accepts C <= MAX_C (2048): at 4096 tokens the
# M = R = 8 partitions are exactly 2048 wide.
MAX_TOKENS = 1 << 12
CONFIGS = np.asarray([[8.0, 8.0], [16.0, 16.0]])


def _check_equivalence(app, corpus) -> None:
    ref = None
    for name in sorted(REDUCE_BACKENDS):
        cfg = JobConfig(num_mappers=8, num_reducers=8, reduce_backend=name)
        ok, ov, dropped = build_job(app, cfg, len(corpus))(corpus)
        got = (collect_results(ok, ov), int(dropped))
        if ref is None:
            ref = got
        elif got != ref:
            raise AssertionError(f"backend {name} diverges from reference")


def main(tokens: int, repeats: int = 2) -> list[str]:
    tokens = min(tokens, MAX_TOKENS)
    rows = ["backends,app,backend,M,R,mean_s"]
    for app_name in ("wordcount", "eximparse"):
        app, corpus = make_app(app_name, tokens)
        _check_equivalence(app, corpus)
        rows.append(f"backends,{app_name},equivalence,ok,,")
        runners = {
            name: JobRunner(app, corpus, reduce_backend=name)
            for name in sorted(REDUCE_BACKENDS)
        }
        profiles = profile_categorical(
            runners, CONFIGS, repeats=repeats,
            param_names=("mappers", "reducers"),
        )
        mean_by_backend = {}
        for name, prof in profiles.items():
            for (m, r), t in zip(prof.params, prof.times):
                rows.append(
                    f"backends,{app_name},{name},{int(m)},{int(r)},{t:.4f}"
                )
            mean_by_backend[name] = float(prof.times.mean())
        best = min(mean_by_backend, key=mean_by_backend.get)
        rows.append(f"backends,{app_name},best,{best},,")
    return rows
