"""Benchmark harness: one section per paper table/figure + beyond-paper.

``PYTHONPATH=src python -m benchmarks.run [--quick] [--tokens N]``

Sections (CSV rows on stdout):
  table1  — Table 1: mean/var prediction error, WordCount + EximParse
  fig3    — Fig. 3: per-experiment predicted vs actual time
  fig4    — Fig. 4: execution-time surface over (M, R) + observed optimum
  tuner   — beyond-paper: regression autotuner vs exhaustive search
  backends— beyond-paper: reduce-backend (jnp/pallas/xla) timing comparison
  phases  — beyond-paper: per-phase telemetry, composed-vs-monolithic models
  cluster — beyond-paper: predictive multi-job scheduling vs FIFO baseline
  elastic — beyond-paper: preemptive regrant scheduling vs admission-only
  pipeline— beyond-paper: pipelined-vs-fused engine speedup + depth-axis MAE
  obs     — beyond-paper: span-tiling validation + drift-alarm-triggered
            refits recovering prediction MAE after a mid-trace platform
            shift (also lands run.trace.json / metrics.json artifacts)
  service — beyond-paper: flash-crowd service stream; burn-rate overload
            control must strictly beat a static admission cap on both
            p99 turnaround and SLO-good goodput (also lands
            service.trace.json / service.prom artifacts)
  combine — beyond-paper: map-side combining — live-engine shuffle-byte
            contraction on skewed WordCount (bit-exactness asserted
            in-bench), contended-fabric makespan win from opening the
            combiner axis, heldout combined-bytes model error (also
            lands combine.trace.json)
  roofline— §Roofline table from the dry-run artifacts
  kernels — per-kernel microbench (us/call, interpret mode)

Every section also lands machine-readable artifacts in ``--outdir``
(default ``experiments/bench/``): ``bench_<section>.csv`` with the
section's rows and ``BENCH_<section>.json`` with summary stats (row count,
wall time, status, any section-provided summary dict, and a provenance
stamp — git SHA, jax version, platform — so ``experiments/bench/``
trajectories are comparable across PRs).

``--check`` turns the committed artifacts into a regression gate: the
fresh summaries are compared against the committed ``BENCH_<sec>.json``
baselines (read before this run overwrites them) and the harness exits
non-zero when any guarded metric — scheduler makespan or SLO attainment,
both from deterministic analytic simulations — regresses by more than
25%.  CI's bench-smoke job runs with ``--check``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ALL_SECTIONS = (
    "table1", "fig3", "fig4", "tuner", "backends", "phases", "cluster",
    "elastic", "pipeline", "obs", "service", "resource", "combine",
    "roofline", "kernels",
)


def provenance() -> dict:
    """Who/what produced this artifact: git SHA, jax version, platform."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 - provenance must never kill a bench
        sha = "unknown"
    try:
        import jax

        jax_version = jax.__version__
        backend = jax.default_backend()
    except Exception:  # noqa: BLE001
        jax_version = backend = "unknown"
    import platform as _platform

    return {
        "git_sha": sha,
        "jax_version": jax_version,
        "jax_backend": backend,
        "python_version": _platform.python_version(),
        "platform": _platform.platform(),
    }


def _kernel_micro() -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.segment_reduce import segment_reduce

    rows = ["kernel,name,us_per_call,derived"]
    rng = np.random.default_rng(0)

    def timeit(fn, *args, reps=3):
        fn(*args)  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / reps * 1e6

    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    us_ref = timeit(lambda a, b, c: attention_ref(a, b, c, causal=True),
                    q, k, v)
    rows.append(f"kernel,attention_ref_256,{us_ref:.0f},xla_reference")
    us_pl = timeit(
        lambda a, b, c: flash_attention(a, b, c, causal=True), q, k, v
    )
    rows.append(
        f"kernel,flash_attention_256,{us_pl:.0f},"
        "interpret_mode_NOT_tpu_timing"
    )
    keys = jnp.asarray(
        np.sort(rng.integers(0, 50, size=(8, 128)).astype(np.int32), axis=1))
    vals = jnp.asarray(rng.integers(0, 9, size=(8, 128)).astype(np.int32))
    us_seg = timeit(segment_reduce, keys, vals)
    rows.append(
        f"kernel,segment_reduce_8x128,{us_seg:.0f},"
        "interpret_mode_NOT_tpu_timing"
    )
    return rows


def run_section(sec: str, tokens: int, repeats: int, outdir: str = ""):
    """Dispatch one section; returns (rows, summary_dict_or_None)."""
    if sec == "table1":
        from benchmarks import table1_prediction_error
        return table1_prediction_error.main(tokens, repeats), None
    if sec == "fig3":
        from benchmarks import fig3_accuracy
        return fig3_accuracy.main(tokens, max(2, repeats - 2)), None
    if sec == "fig4":
        from benchmarks import fig4_surface
        return fig4_surface.main(tokens, max(2, repeats - 2)), None
    if sec == "tuner":
        from benchmarks import tuner_vs_exhaustive
        return tuner_vs_exhaustive.main(tokens), None
    if sec == "backends":
        from benchmarks import backends_compare
        return backends_compare.main(tokens, max(2, repeats - 2)), None
    if sec == "phases":
        from benchmarks import phase_bench
        return phase_bench.main(tokens, max(2, repeats - 2))
    if sec == "cluster":
        from benchmarks import cluster_bench
        return cluster_bench.main(tokens, repeats)
    if sec == "elastic":
        from benchmarks import elastic_bench
        return elastic_bench.main(tokens, repeats)
    if sec == "pipeline":
        from benchmarks import pipeline_bench
        return pipeline_bench.main(tokens, repeats)
    if sec == "obs":
        from benchmarks import obs_bench
        return obs_bench.main(tokens, repeats, outdir=outdir or None)
    if sec == "service":
        from benchmarks import service_bench
        return service_bench.main(tokens, repeats, outdir=outdir or None)
    if sec == "resource":
        from benchmarks import resource_bench
        return resource_bench.main(tokens, repeats, outdir=outdir or None)
    if sec == "combine":
        from benchmarks import combine_bench
        return combine_bench.main(tokens, repeats, outdir=outdir or None)
    if sec == "roofline":
        from benchmarks import roofline
        return roofline.main(), None
    if sec == "kernels":
        return _kernel_micro(), None
    raise ValueError(f"unknown section {sec!r}; expected {ALL_SECTIONS}")


#: --check regression gate: relative tolerance on the guarded metrics.
CHECK_TOLERANCE = 0.25


def _walk_metrics(summary, path=""):
    """Yield (dotted_path, key, value) for every guarded metric leaf."""
    if isinstance(summary, dict):
        for k, v in summary.items():
            p = f"{path}.{k}" if path else str(k)
            if k in (
                "makespan_s", "slo_attainment", "speedup", "recovery",
                "p99_turnaround_s", "goodput", "makespan_win",
                "cpu_mae_pct", "net_mae_pct", "net_reduction",
                "contended_win", "combined_net_mae_pct",
            ) and isinstance(v, (int, float)):
                yield p, k, float(v)
            else:
                yield from _walk_metrics(v, p)


def load_committed(outdir: str, sections) -> tuple[dict, list[str]]:
    """The BENCH_<sec>.json summaries as committed, read *before* this
    run overwrites them — the baseline the --check gate compares against.

    Returns ``(committed, malformed)``: a baseline file that exists but
    does not parse as a JSON object (truncated commit, merge damage) must
    not crash the gate with a raw traceback, nor silently pass as if no
    baseline existed — it is reported as ``_check_warn,malformed_baseline``
    and excluded from comparison, same exit behavior as a missing one.
    """
    committed: dict = {}
    malformed: list[str] = []
    for sec in sections:
        path = os.path.join(outdir, f"BENCH_{sec}.json")
        try:
            with open(path) as f:
                doc = json.load(f)
        except OSError:
            continue
        except (json.JSONDecodeError, UnicodeDecodeError, ValueError):
            malformed.append(sec)
            continue
        if not isinstance(doc, dict):
            malformed.append(sec)
            continue
        committed[sec] = doc
    return committed, malformed


def check_regressions(committed: dict, fresh: dict) -> list[str]:
    """Compare guarded metrics (makespan_s / slo_attainment / speedup) of
    each fresh section summary against the committed baseline.

    A regression is a makespan (or the service section's p99 turnaround,
    or the resource section's heldout CPU/net model error) more than
    ``CHECK_TOLERANCE`` above the committed value, or an SLO
    attainment (or pipelined-mode speedup, the obs section's
    drift-recovery ratio, the service section's SLO-good goodput, or the
    resource section's blind-over-aware makespan win) more than
    ``CHECK_TOLERANCE`` below it.  Only metric paths present in
    both summaries compare; the guarded sections (cluster, elastic) are
    deterministic analytic simulations, so drift means a real behavior
    change, not noise — the pipeline section's speedup is measured
    wall-clock, which is why its tolerance band is the same generous 25%.
    """
    problems: list[str] = []
    for sec, old in committed.items():
        new = fresh.get(sec)
        if new is None or old.get("status") != "ok":
            continue
        if new.get("status") != "ok":
            problems.append(f"{sec}: section now fails "
                            f"({new.get('error', 'unknown error')})")
            continue
        old_metrics = {p: (k, v) for p, k, v in
                       _walk_metrics(old.get("summary", {}))}
        new_metrics = {p: (k, v) for p, k, v in
                       _walk_metrics(new.get("summary", {}))}
        for p, (kind, old_v) in sorted(old_metrics.items()):
            if p not in new_metrics:
                continue
            new_v = new_metrics[p][1]
            if kind in (
                "makespan_s", "p99_turnaround_s", "cpu_mae_pct",
                "net_mae_pct", "net_reduction", "combined_net_mae_pct",
            ) and (
                new_v > old_v * (1 + CHECK_TOLERANCE)
            ):
                problems.append(
                    f"{sec}: {p} regressed {old_v:.3f} -> {new_v:.3f} "
                    f"(+{(new_v / max(old_v, 1e-12) - 1) * 100:.0f}%)"
                )
            elif kind in (
                "slo_attainment", "speedup", "recovery", "goodput",
                "makespan_win", "contended_win",
            ) and new_v < old_v * (1 - CHECK_TOLERANCE):
                problems.append(
                    f"{sec}: {p} regressed {old_v:.3f} -> {new_v:.3f} "
                    f"(-{(1 - new_v / max(old_v, 1e-12)) * 100:.0f}%)"
                )
    return problems


def write_artifacts(
    outdir: str, sec: str, rows: list[str], summary: dict
) -> None:
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"bench_{sec}.csv"), "w") as f:
        f.write("\n".join(rows) + ("\n" if rows else ""))
    path = os.path.join(outdir, f"BENCH_{sec}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller corpora / fewer repeats")
    ap.add_argument("--tokens", type=int, default=None)
    ap.add_argument("--sections", default="all",
                    help="comma list: " + ",".join(ALL_SECTIONS))
    ap.add_argument("--outdir", default="experiments/bench",
                    help="where bench_<sec>.csv + BENCH_<sec>.json land "
                         "(empty string disables)")
    ap.add_argument("--check", action="store_true",
                    help="bench-regression guard: compare the fresh "
                         "summaries against the committed BENCH_<sec>.json "
                         "baselines and exit non-zero on a >25%% makespan "
                         "or SLO-attainment regression (CI smoke gate)")
    ap.add_argument("--log-level", default="info",
                    choices=("debug", "info", "warning", "error"))
    ap.add_argument("--log-json", action="store_true",
                    help="section progress on stderr as JSON lines "
                         "instead of text (CSV rows stay on stdout)")
    args = ap.parse_args()
    from repro.obs import get_logger

    log = get_logger(
        "bench", level=args.log_level, json_lines=args.log_json
    )
    tokens = args.tokens or (1 << 14 if args.quick else 1 << 16)
    repeats = 2 if args.quick else 5
    sections = (
        list(ALL_SECTIONS) if args.sections == "all"
        else args.sections.split(",")
    )
    rows: list[str] = []
    t_start = time.time()
    stamp = provenance()
    committed, malformed = (
        load_committed(args.outdir, sections)
        if args.check and args.outdir else ({}, [])
    )
    fresh: dict[str, dict] = {}
    for sec in sections:
        t0 = time.time()
        log.info("section_start", section=sec, msg=f"running {sec}...")
        sec_rows: list[str] = []
        summary: dict = {
            "section": sec,
            "quick": args.quick,
            "tokens": tokens,
            "status": "ok",
            "provenance": stamp,
        }
        try:
            sec_rows, sec_summary = run_section(
                sec, tokens, repeats, args.outdir
            )
            if sec_summary:
                summary["summary"] = sec_summary
        except Exception as e:  # noqa: BLE001
            summary["status"] = "error"
            summary["error"] = f"{type(e).__name__}: {e}"
            sec_rows = sec_rows or []
            sec_rows.append(f"_error,{sec},{type(e).__name__},{e}")
            log.error(
                "section_error", section=sec, error=summary["error"],
                msg=f"{sec} failed: {summary['error']}",
            )
        summary["n_rows"] = len(sec_rows)
        summary["wall_seconds"] = round(time.time() - t0, 3)
        rows += sec_rows
        fresh[sec] = summary
        if summary["status"] == "ok":
            rows.append(f"_timing,{sec},{summary['wall_seconds']:.1f}s,")
            log.info(
                "section_done", section=sec,
                wall_seconds=summary["wall_seconds"],
                n_rows=summary["n_rows"],
                msg=f"{sec} done in {summary['wall_seconds']:.1f}s "
                    f"({summary['n_rows']} rows)",
            )
        if args.outdir:
            write_artifacts(args.outdir, sec, sec_rows, summary)
    rows.append(f"_timing,total,{time.time() - t_start:.1f}s,")
    problems = []
    if args.check:
        problems = check_regressions(committed, fresh)
        checked = sorted(
            sec for sec in committed
            if any(_walk_metrics(committed[sec].get("summary", {})))
        )
        rows.append(
            f"_check,sections={'+'.join(checked) or 'none'},"
            f"regressions={len(problems)},tolerance={CHECK_TOLERANCE}"
        )
        # A section with no committed BENCH_<sec>.json has nothing to gate
        # against; warn instead of silently passing so a forgotten commit
        # of the baseline artifact is visible in the check output.
        rows += [
            f"_check_warn,malformed_baseline,{sec}" for sec in malformed
        ]
        rows += [
            f"_check_warn,missing_baseline,{sec}"
            for sec in sections
            if sec not in committed and sec not in malformed
        ]
        rows += [f"_check_fail,{p}" for p in problems]
    print("\n".join(rows))
    if any(r.startswith("_error") for r in rows) or problems:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
