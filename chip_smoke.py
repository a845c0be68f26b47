"""Chip smoke test: the MapReduce engine's main path on a TPU v5e.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the 4-chip sharded path

One chip runs, through the normal entry points (``build_job``,
``ExecutionPlan``, ``repro.core.profile_experiments`` / ``fit``):

* ``wordcount`` — WordCount over 2^26 Zipf(1.3) words (a 256 MiB int32
  stream) at M=20, R=5: fused with the ``jnp`` and ``xla`` reduce
  backends, each with the combiner off and on; pipelined at overlap depth
  2; traced once (per-phase walls).  Reference: ``np.bincount``.
* ``exim`` — Exim mainlog parsing over the whole records of 2^26 tokens
  (2^26 - 1 tokens), ``xla`` backend.  Reference: the sizes summed per
  transaction id over every record in numpy.
* ``pallas`` — the Pallas reduce backend, compiled (the HLO must hold a
  ``tpu_custom_call``), with and without the combiner, on a job whose
  partitions fit the kernels' ``MAX_C``; bit-exact against ``jnp``.
* ``loop`` — the paper's profile -> fit -> predict loop over WordCount at
  2^24 words, M in {10, 20, 40} x R in {2, 4, 8}, one configuration
  held out of the fit.

``--chips 4`` runs only ``ExecutionPlan.sharded`` on a default
``jax.make_mesh`` 4-chip mesh (WordCount, 2^26 words, W=4) against numpy
and, bit for bit, against the emulated ``all_to_all`` fused mode on one
chip.

Every program is compiled ahead of time in a thread pool, so compiles
overlap one another and the runs.  Each mode prints one line: compile
seconds, warm wall seconds (after ``block_until_ready``), ``dropped`` and
pass/fail against the reference.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed on a TPU; otherwise the script exits non-zero without it.  There
is no CPU path.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

WC_TOKENS = 1 << 26
WC_VOCAB = 4096
WC_M, WC_R = 20, 5
EXIM_TXNS = 1024
EXIM_TOKENS = WC_TOKENS - WC_TOKENS % 3  # whole [txn, event, size] records
PALLAS_TOKENS = 1 << 12     # M=R=8: partitions exactly MAX_C wide
PALLAS_M = PALLAS_R = 8
LOOP_TOKENS = 1 << 24
LOOP_GRID = [(m, r) for m in (10, 20, 40) for r in (2, 4, 8)]
LOOP_HELDOUT = (20, 4)
LOOP_REPEATS = 3
CAPACITY_FACTOR = 4.0
SEED = 0


class Smoke:
    """Compile pool, per-mode report lines and the overall verdict."""

    def __init__(self, workers: int):
        self.pool = ThreadPoolExecutor(max_workers=workers)
        self.ok = True

    def compile(self, jitted, *args):
        """Trace and lower here, then submit the XLA compile; the future
        yields ``(compiled, lower + compile seconds)``.  Tracing stays on
        this thread: it runs Python (lazy imports among it) that is not
        safe to run from several threads at once."""
        t0 = time.perf_counter()
        lowered = jitted.lower(*args)
        lower_s = time.perf_counter() - t0

        def work():
            t0 = time.perf_counter()
            compiled = lowered.compile()
            return compiled, lower_s + time.perf_counter() - t0

        return self.pool.submit(work)

    def report(self, phase: str, mode: str, passed: bool, **fields):
        self.ok &= bool(passed)
        parts = [f"phase={phase}", f"mode={mode}"]
        parts += [f"{k}={v}" for k, v in fields.items()]
        parts.append("check=pass" if passed else "check=FAIL")
        print(" ".join(parts), flush=True)

    def guarded(self, phase: str, fn, *args):
        """Run one phase; an exception fails it without hiding the others."""
        try:
            fn(*args)
        except Exception as e:  # noqa: BLE001
            self.report(phase, "error", False,
                        error=repr(f"{type(e).__name__}: {e}"[:400]))


def timed(fn, *args):
    """Warm wall seconds of one call, fenced by ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def counts_match(ok, ov, want: dict) -> bool:
    from repro.mapreduce import collect_results

    return collect_results(ok, ov) == want


def exim_reference(log, n_txn: int) -> dict:
    """Sum of sizes per transaction id over every ``[txn, event, size]``
    record of the log."""
    import numpy as np

    from repro.mapreduce.apps import RECORD_WIDTH

    rec = log.reshape(-1, RECORD_WIDTH)
    keys, sizes = rec[:, 0], rec[:, 2]
    present = np.flatnonzero(np.bincount(keys, minlength=n_txn))
    sums = np.bincount(keys, weights=sizes, minlength=n_txn)
    return {int(k): int(sums[k]) for k in present}


def wordcount_reference(corpus, vocab: int) -> dict:
    import numpy as np

    counts = np.bincount(corpus, minlength=vocab)
    return {int(k): int(v) for k, v in enumerate(counts) if v}


def _cfg(M, R, **kw):
    from repro.mapreduce import JobConfig

    return JobConfig(num_mappers=M, num_reducers=R,
                     capacity_factor=CAPACITY_FACTOR, **kw)


# --------------------------------------------------------------- one chip


def submit_wordcount(smoke: Smoke, n: int):
    import jax
    import jax.numpy as jnp

    from repro.mapreduce import build_job, wordcount

    app, tok = wordcount(WC_VOCAB), jax.ShapeDtypeStruct((n,), jnp.int32)
    modes = {}
    for backend in ("jnp", "xla"):
        for combiner in (False, True):
            cfg = _cfg(WC_M, WC_R, reduce_backend=backend, combiner=combiner)
            modes[f"fused_{backend}_combiner{int(combiner)}"] = \
                smoke.compile(build_job(app, cfg, n), tok)
    cfg = _cfg(WC_M, WC_R, overlap_depth=2)
    modes["pipelined_jnp_depth2"] = smoke.compile(build_job(app, cfg, n), tok)
    return modes


def run_wordcount(smoke: Smoke, n: int, futures: dict):
    import jax

    from repro.mapreduce import build_job, wordcount, wordcount_corpus
    from repro.telemetry import PhaseRecorder

    t0 = time.perf_counter()
    corpus = wordcount_corpus(n, vocab_size=WC_VOCAB, zipf_a=1.3, seed=SEED)
    want = wordcount_reference(corpus, WC_VOCAB)
    tok = jax.device_put(corpus)
    print(f"phase=wordcount setup tokens={n} M={WC_M} R={WC_R} "
          f"datagen_s={time.perf_counter() - t0:.3f}", flush=True)
    for mode, fut in futures.items():
        compiled, compile_s = fut.result()
        (ok, ov, d), _ = timed(compiled, tok)
        passed = int(d) == 0 and counts_match(ok, ov, want)
        del ok, ov
        _, warm_s = timed(compiled, tok)
        smoke.report("wordcount", mode, passed, compile_s=f"{compile_s:.3f}",
                     warm_s=f"{warm_s:.4f}", dropped=int(d))
    # Traced: phase-fenced programs compiled on the first call.
    rec = PhaseRecorder()
    job = build_job(wordcount(WC_VOCAB), _cfg(WC_M, WC_R), n, recorder=rec)
    (ok, ov, d), first_s = timed(job, tok)
    passed = int(d) == 0 and counts_match(ok, ov, want)
    del ok, ov
    _, warm_s = timed(job, tok)
    walls = " ".join(f"{p.phase}_s={p.wall_s:.4f}" for p in rec.last.phases)
    smoke.report("wordcount", "traced_jnp", passed,
                 compile_s=f"{first_s - warm_s:.3f}", warm_s=f"{warm_s:.4f}",
                 dropped=int(d), phases=f"[{walls}]")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase=wordcount peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def submit_exim(smoke: Smoke, n: int):
    import jax
    import jax.numpy as jnp

    from repro.mapreduce import build_job, eximparse

    cfg = _cfg(WC_M, WC_R, reduce_backend="xla")
    return smoke.compile(build_job(eximparse(EXIM_TXNS), cfg, n),
                         jax.ShapeDtypeStruct((n,), jnp.int32))


def run_exim(smoke: Smoke, n: int, future):
    import jax

    from repro.mapreduce import exim_mainlog

    t0 = time.perf_counter()
    log = exim_mainlog(n, n_transactions=EXIM_TXNS, seed=SEED)
    want = exim_reference(log, EXIM_TXNS)
    tok = jax.device_put(log)
    print(f"phase=exim setup tokens={n} M={WC_M} R={WC_R} "
          f"datagen_s={time.perf_counter() - t0:.3f}", flush=True)
    compiled, compile_s = future.result()
    (ok, ov, d), _ = timed(compiled, tok)
    passed = int(d) == 0 and counts_match(ok, ov, want)
    del ok, ov
    _, warm_s = timed(compiled, tok)
    smoke.report("exim", "fused_xla_combiner0", passed,
                 compile_s=f"{compile_s:.3f}", warm_s=f"{warm_s:.4f}",
                 dropped=int(d))


def submit_pallas(smoke: Smoke, n: int):
    import jax
    import jax.numpy as jnp

    from repro.mapreduce import build_job, wordcount

    app, tok = wordcount(WC_VOCAB), jax.ShapeDtypeStruct((n,), jnp.int32)
    return {
        (backend, combiner): smoke.compile(build_job(
            app, _cfg(PALLAS_M, PALLAS_R, reduce_backend=backend,
                      combiner=combiner), n), tok)
        for backend in ("pallas", "jnp") for combiner in (False, True)
    }


def run_pallas(smoke: Smoke, n: int, futures: dict):
    import jax
    import numpy as np

    from repro.mapreduce import wordcount_corpus

    corpus = wordcount_corpus(n, vocab_size=WC_VOCAB, zipf_a=1.3, seed=SEED)
    want = wordcount_reference(corpus, WC_VOCAB)
    tok = jax.device_put(corpus)
    for combiner in (False, True):
        compiled, compile_s = futures[("pallas", combiner)].result()
        ref_compiled, _ = futures[("jnp", combiner)].result()
        (ok, ov, d), _ = timed(compiled, tok)
        rk, rv, rd = ref_compiled(tok)
        custom_call = "tpu_custom_call" in compiled.as_text()
        exact = (np.array_equal(np.asarray(ok), np.asarray(rk))
                 and np.array_equal(np.asarray(ov), np.asarray(rv))
                 and int(d) == int(rd))
        passed = (custom_call and exact and int(d) == 0
                  and counts_match(ok, ov, want))
        _, warm_s = timed(compiled, tok)
        smoke.report("pallas", f"fused_pallas_combiner{int(combiner)}",
                     passed, compile_s=f"{compile_s:.3f}",
                     warm_s=f"{warm_s:.4f}", dropped=int(d), tokens=n,
                     partition_width=int(ok.shape[1]),
                     tpu_custom_call=custom_call, bit_exact_vs_jnp=exact)


def submit_loop(smoke: Smoke, n: int):
    import jax
    import jax.numpy as jnp

    from repro.mapreduce import build_job, wordcount

    app, tok = wordcount(WC_VOCAB), jax.ShapeDtypeStruct((n,), jnp.int32)
    return {mr: smoke.compile(build_job(app, _cfg(*mr), n), tok)
            for mr in LOOP_GRID}


def run_loop(smoke: Smoke, n: int, futures: dict):
    import jax
    import numpy as np

    from repro.core import fit, profile_experiments
    from repro.mapreduce import wordcount_corpus

    corpus = wordcount_corpus(n, vocab_size=WC_VOCAB, zipf_a=1.3, seed=SEED)
    want = wordcount_reference(corpus, WC_VOCAB)
    tok = jax.device_put(corpus)
    compile_s, correct = 0.0, True
    for mr, fut in futures.items():
        compiled, dt = fut.result()
        compile_s += dt
        ok, ov, d = jax.block_until_ready(compiled(tok))  # warm-up + check
        correct &= int(d) == 0 and counts_match(ok, ov, want)
        del ok, ov

    def run_job(config) -> float:
        compiled, _ = futures[(int(config[0]), int(config[1]))].result()
        return timed(compiled, tok)[1]

    train = np.asarray([mr for mr in LOOP_GRID if mr != LOOP_HELDOUT],
                       np.float64)
    prof = profile_experiments(run_job, train, repeats=LOOP_REPEATS,
                               param_names=("mappers", "reducers"))
    model = fit(prof.params, prof.times)
    held = np.asarray([LOOP_HELDOUT], np.float64)
    actual = float(np.mean([run_job(LOOP_HELDOUT)
                            for _ in range(LOOP_REPEATS)]))
    pred = float(np.asarray(model.predict(held))[0])
    err = abs(pred - actual) / actual * 100
    smoke.report("loop", "profile_fit_predict",
                 correct and np.isfinite(pred) and np.isfinite(model.coef).all(),
                 tokens=n, configs=len(LOOP_GRID), compile_s=f"{compile_s:.3f}",
                 train_mape_pct=f"{model.train_mape:.3f}",
                 heldout=f"M{LOOP_HELDOUT[0]}R{LOOP_HELDOUT[1]}",
                 heldout_pred_s=f"{pred:.5f}", heldout_actual_s=f"{actual:.5f}",
                 heldout_err_pct=f"{err:.3f}")


def one_chip(smoke: Smoke, sizes: dict):
    # Submit every compile first (longest-running first), then run the
    # phases in order; each waits only for its own programs.
    loop = submit_loop(smoke, sizes["loop"])
    wc = submit_wordcount(smoke, sizes["wordcount"])
    exim = submit_exim(smoke, sizes["exim"])
    pallas = submit_pallas(smoke, sizes["pallas"])
    smoke.guarded("wordcount", run_wordcount, smoke, sizes["wordcount"], wc)
    smoke.guarded("exim", run_exim, smoke, sizes["exim"], exim)
    smoke.guarded("pallas", run_pallas, smoke, sizes["pallas"], pallas)
    smoke.guarded("loop", run_loop, smoke, sizes["loop"], loop)


# ------------------------------------------------------------ four chips


def four_chips(smoke: Smoke, n: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.mapreduce import ExecutionPlan, wordcount, wordcount_corpus

    W = 4
    mesh = jax.make_mesh((W,), ("workers",))
    app = wordcount(WC_VOCAB)
    tok = jax.ShapeDtypeStruct((n,), jnp.int32)
    tok1 = jax.ShapeDtypeStruct(
        (n,), jnp.int32, sharding=SingleDeviceSharding(jax.devices()[0]))
    sharded = smoke.compile(ExecutionPlan(
        app, _cfg(WC_M, WC_R, num_workers=W), n).sharded(mesh), tok)
    emulated = smoke.compile(ExecutionPlan(
        app, _cfg(WC_M, WC_R, num_workers=W, shuffle_backend="all_to_all"),
        n).fused(), tok1)
    corpus = wordcount_corpus(n, vocab_size=WC_VOCAB, zipf_a=1.3, seed=SEED)
    want = wordcount_reference(corpus, WC_VOCAB)
    job, compile_s = sharded.result()
    (ok, ov, d), _ = timed(job, corpus)
    passed = int(d) == 0 and counts_match(ok, ov, want)
    _, warm_s = timed(job, corpus)
    smoke.report("sharded", f"shard_map_all_to_all_W{W}", passed,
                 compile_s=f"{compile_s:.3f}", warm_s=f"{warm_s:.4f}",
                 dropped=int(d), tokens=n, mesh=dict(mesh.shape),
                 axis_types=str(mesh.axis_types))
    ok, ov, d = np.asarray(ok), np.asarray(ov), int(d)
    job, compile_s = emulated.result()
    (ek, ev, ed), _ = timed(job, jax.device_put(corpus, tok1.sharding))
    exact = (np.array_equal(np.asarray(ek), ok)
             and np.array_equal(np.asarray(ev), ov) and int(ed) == d)
    smoke.report("sharded", f"emulated_all_to_all_W{W}_one_chip", exact,
                 compile_s=f"{compile_s:.3f}", dropped=int(ed),
                 bit_exact_vs_sharded=exact)


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip sharded all_to_all path")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this script has no CPU path",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX sees {len(devices)}", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    smoke = Smoke(workers=min(8, os.cpu_count() or 1))
    try:
        if args.chips == 4:
            smoke.guarded("sharded", four_chips, smoke, WC_TOKENS)
        else:
            one_chip(smoke, {"wordcount": WC_TOKENS, "exim": EXIM_TOKENS,
                             "pallas": PALLAS_TOKENS, "loop": LOOP_TOKENS})
    finally:
        smoke.pool.shutdown(wait=True, cancel_futures=True)
    print(f"total_s={time.perf_counter() - t0:.3f} ok={smoke.ok}", flush=True)
    if not smoke.ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
