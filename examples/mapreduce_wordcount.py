"""Run the TPU-native MapReduce engine end-to-end: WordCount over a Zipf
corpus, through one ExecutionPlan whose *mode* is picked by the flags —
fused single-controller by default, the sharded (all_to_all) mesh mode
with more than one worker, the software-pipelined wave schedule with
--depth 2+, and the phase-fenced traced mode (per-phase wall times, on
any path) with --phase-times.

    PYTHONPATH=src python examples/mapreduce_wordcount.py
    # per-phase wall times (works on the sharded path too):
    PYTHONPATH=src python examples/mapreduce_wordcount.py --phase-times
    # software-pipelined wave schedule (bit-exact vs fused):
    PYTHONPATH=src python examples/mapreduce_wordcount.py --depth 4
    # map-side combining (bit-exact; contracts shuffle bytes — pair
    # --combiner with --phase-times to see the combine phase counters):
    PYTHONPATH=src python examples/mapreduce_wordcount.py \
        --combiner --phase-times
    # multi-worker shuffle:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/mapreduce_wordcount.py --workers 4
"""

import argparse
import time

import jax

from repro.mapreduce import (
    ExecutionPlan,
    JobConfig,
    collect_results,
    wordcount,
    wordcount_corpus,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=1 << 16)
    ap.add_argument("--mappers", type=int, default=20)
    ap.add_argument("--reducers", type=int, default=5)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--depth", type=int, default=1,
                    help="overlap depth: group this many waves per "
                         "software-pipeline step (1 = serial fused)")
    ap.add_argument("--combiner", action="store_true",
                    help="map-side combine: pre-aggregate each map "
                         "task's pairs before the shuffle (bit-exact "
                         "for WordCount's sum; contracts shuffle bytes "
                         "hard on the Zipf-skewed corpus)")
    ap.add_argument("--phase-times", action="store_true",
                    help="run the traced mode: fence + wall-clock each "
                         "phase (three fenced mesh programs when sharded)")
    args = ap.parse_args()
    if args.depth > 1 and args.workers > 1:
        ap.error("--depth > 1 is a single-controller schedule; "
                 "it does not compose with --workers > 1")
    corpus = wordcount_corpus(args.tokens, vocab_size=4096, seed=0)
    app = wordcount(4096)
    cfg = JobConfig(
        num_mappers=args.mappers, num_reducers=args.reducers,
        num_workers=args.workers, overlap_depth=args.depth,
        combiner=args.combiner,
    )
    recorder = None
    if args.phase_times:
        from repro.telemetry import PhaseRecorder

        recorder = PhaseRecorder()
    plan = ExecutionPlan(app, cfg, len(corpus))
    if args.workers > 1:
        mesh = jax.make_mesh(
            (args.workers,), ("workers",),
            axis_types=(jax.sharding.AxisType.Auto,),
        )
        job = plan.sharded(mesh, recorder=recorder)
        path = f"sharded all_to_all over {args.workers} workers"
    elif recorder is not None:
        job = plan.traced(recorder)  # picks up cfg.overlap_depth
        path = "single-controller (traced)"
        if args.depth > 1:
            path += f", pipelined depth={args.depth}"
    elif args.depth > 1:
        job = plan.pipelined()
        path = f"single-controller (pipelined, depth={args.depth})"
    else:
        job = plan.fused()
        path = "single-controller (fused)"
    jax.block_until_ready(job(corpus))  # job setup (compile)
    t0 = time.perf_counter()
    ok, ov, dropped = job(corpus)
    jax.block_until_ready(ov)
    dt = time.perf_counter() - t0
    counts = collect_results(ok, ov)
    top = sorted(counts.items(), key=lambda kv: -kv[1])[:10]
    if args.combiner:
        path += ", combiner on"
    print(f"{args.tokens} tokens, M={cfg.num_mappers} R={cfg.num_reducers} "
          f"({cfg.map_waves}/{cfg.reduce_waves} waves), {path}")
    print(f"execution time: {dt * 1e3:.1f}ms; dropped={int(dropped)}")
    if recorder is not None:
        times = recorder.last.phase_times()
        print("phase walls: " + ", ".join(
            f"{k}={v * 1e3:.1f}ms" for k, v in times.items()
        ))
    print("top words:", top)
    assert sum(counts.values()) == args.tokens


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
