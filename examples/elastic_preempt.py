"""Elastic execution walkthrough: preempt, snapshot, regrant, resume.

    PYTHONPATH=src python examples/elastic_preempt.py

Runs a WordCount job through the wave-steppable engine, preempts it
mid-map, persists the wave-boundary snapshot through the checkpoint
manager, restores it template-free ("a different process"), *regrants*
the job from 2 workers to 4, resumes — and verifies the result is
bit-identical to the uninterrupted 2-worker run.  Then prices the
regrant with the cost model the ``predict-elastic`` scheduler uses.
"""

import tempfile

import numpy as np

from repro.checkpoint import CheckpointManager
from repro.cluster import AnalyticOracle
from repro.elastic import (
    RegrantCostModel,
    ResumableJob,
    load_snapshot,
    run_resumable,
    save_snapshot,
)
from repro.mapreduce import JobConfig, collect_results, wordcount, \
    wordcount_corpus


def main():
    corpus = wordcount_corpus(6000, vocab_size=211, seed=1)
    app = wordcount(211)
    cfg = JobConfig(num_mappers=8, num_reducers=4, num_workers=2,
                    capacity_factor=8.0)
    # ResumableJob is the resumable *mode* of the one ExecutionPlan the
    # fused/traced/sharded paths also run (repro.mapreduce.plan), so the
    # wave-stepped results below are bit-exact vs build_job by
    # construction.
    job = ResumableJob(app, cfg, len(corpus))

    # Reference: the uninterrupted run.
    ref = run_resumable(job, corpus)
    ok0, ov0, d0 = job.result(ref)
    print(f"[elastic] uninterrupted: {ref.cursor.waves_executed} "
          f"wave-boundary steps, dropped={int(d0)}")

    # Preempt after 2 map waves, snapshot through the checkpoint manager.
    state = run_resumable(job, corpus, preempt_after=2)
    c = state.cursor
    print(f"[elastic] preempted at boundary: map {c.map_tasks_done}/"
          f"{c.mappers} tasks done, shuffled={c.shuffled}")
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=3)
        step, save_s = save_snapshot(mgr, state)
        restored, _, restore_s = load_snapshot(mgr)  # template-free
        print(f"[elastic] snapshot step {step}: save {save_s * 1e3:.1f}ms,"
              f" restore {restore_s * 1e3:.1f}ms")

    # Re-plan the remaining waves under twice the workers and resume.
    restored = job.regrant(restored, 4)
    done = run_resumable(job, corpus, state=restored)
    ok1, ov1, d1 = job.result(done)
    assert np.array_equal(np.asarray(ok0), np.asarray(ok1))
    assert np.array_equal(np.asarray(ov0), np.asarray(ov1))
    assert int(d0) == int(d1)
    assert collect_results(ok1, ov1) == collect_results(ok0, ov0)
    print("[elastic] resumed under W=4: bit-identical to the W=2 run")

    # Price the same regrant the way the scheduler would: predicted
    # remaining time under each grant + the measured checkpoint cost.
    oracle = AnalyticOracle(noise=0.0)
    cost = RegrantCostModel()
    cost.record_overhead(save_s, restore_s)
    progress = c.progress()
    decision = cost.evaluate(
        t_total_current=oracle.time("wordcount", "jnp", len(corpus),
                                    c.mappers, c.reducers, 2),
        t_total_new=oracle.time("wordcount", "jnp", len(corpus),
                                c.mappers, c.reducers, 4),
        progress=progress, current_workers=2, new_workers=4,
    )
    print(f"[elastic] regrant 2->4: remaining {decision.t_remaining_current:.3f}s"
          f" -> {decision.t_remaining_new:.3f}s + overhead "
          f"{decision.overhead_s * 1e3:.1f}ms, gain {decision.gain_s:+.3f}s,"
          f" worth_it={decision.worth_it}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
