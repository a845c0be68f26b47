"""Sharded (all_to_all) MapReduce path — needs >1 device, so runs in a
subprocess with forced host device count.

Covers the multi-device halves of the ExecutionPlan mode-equivalence
story (the W=1 in-process halves live in tests/test_plan.py): the real
4-device mesh mode vs the single-controller modes, the emulated
(resumable) collective vs the real one, per-phase wall times on the
sharded path, and cross-shard-reduced overflow counters.
"""

import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from collections import Counter
from repro.mapreduce import (ExecutionPlan, JobConfig, build_job,
                             build_job_sharded, collect_results, wordcount,
                             wordcount_corpus)
from repro.telemetry import PhaseRecorder

mesh = jax.make_mesh((4,), ("workers",))
corpus = wordcount_corpus(5000, vocab_size=129, seed=11)
app = wordcount(129)
# pallas runs with the combiner: it keeps every kernel row (map-task rows
# of 1000 pairs, reduce partitions of 1032 slots) within the kernels'
# MAX_C, which the uncombined 8000-slot partitions here would exceed
for (M, R), backend, combiner in [((8, 6), "jnp", False),
                                  ((5, 9), "pallas", True),
                                  ((4, 4), "xla", False)]:
    cfg = JobConfig(num_mappers=M, num_reducers=R, num_workers=4,
                    capacity_factor=12.0, reduce_backend=backend,
                    combiner=combiner)
    plan = ExecutionPlan(app, cfg, len(corpus))
    ok, ov, dropped = plan.sharded(mesh)(corpus)
    assert int(dropped) == 0, (M, R)
    got = collect_results(ok, ov)
    want = dict(Counter(corpus.tolist()))
    assert got == want, (M, R, len(got), len(want))
    # equivalence with the single-controller path
    cfg1 = JobConfig(num_mappers=M, num_reducers=R, capacity_factor=12.0)
    ok1, ov1, d1 = build_job(app, cfg1, len(corpus))(corpus)
    assert collect_results(ok1, ov1) == got
    # emulated collective (the resumable/fused a2a mode at W=4) must be
    # bit-exact against the real 4-device mesh run (one backend is
    # enough: the emulated/real split is shuffle-side, backend-agnostic)
    if backend == "jnp":
        a2a = JobConfig(num_mappers=M, num_reducers=R, num_workers=4,
                        capacity_factor=12.0, reduce_backend=backend,
                        shuffle_backend="all_to_all")
        plan_a2a = ExecutionPlan(app, a2a, len(corpus))
        ok_e, ov_e, d_e = plan_a2a.fused()(corpus)
        assert np.array_equal(np.asarray(ok_e), np.asarray(ok)), (M, R)
        assert np.array_equal(np.asarray(ov_e), np.asarray(ov)), (M, R)
        assert int(d_e) == int(dropped), (M, R)
# config-driven route: shuffle backend selected via JobConfig
cfg = JobConfig(num_mappers=6, num_reducers=5, num_workers=4,
                capacity_factor=12.0, shuffle_backend="all_to_all")
ok, ov, d = build_job(app, cfg, len(corpus), mesh=mesh)(corpus)
assert int(d) == 0
assert collect_results(ok, ov) == dict(Counter(corpus.tolist()))
# per-phase wall times on the REAL sharded path: three fenced mesh
# programs, counters cross-shard reduced, same outputs as the fused mode
rec = PhaseRecorder()
ok_t, ov_t, d_t = build_job(app, cfg, len(corpus), mesh=mesh,
                            recorder=rec)(corpus)
assert np.array_equal(np.asarray(ok_t), np.asarray(ok))
assert int(d_t) == 0
trace = rec.last
assert trace.phase_names() == ["map", "shuffle", "reduce"]
assert all(p.wall_s > 0 for p in trace.phases)
assert trace.check_conservation() == []
assert trace.counter("map", "pairs_emitted") == len(corpus)
# per-phase dropped counters, cross-shard reduced: max-skew corpus (one
# key) overflows the per-(src, dst) send buffers at W=4
skew = np.zeros(600, dtype=np.int32)
cfg = JobConfig(num_mappers=2, num_reducers=4, num_workers=4,
                capacity_factor=1.0)
ok, ov, d, stats = build_job_sharded(app, cfg, len(skew), mesh,
                                     counters=True)(skew)
assert stats["dropped_per_worker"].shape == (4, 2)
assert stats["dropped_send"] + stats["dropped_recv"] == int(d) > 0
assert stats["dropped_send"] > 0  # skew saturates the send stage
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_engine_matches_global(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert "SHARDED_OK" in proc.stdout, proc.stderr[-3000:]
