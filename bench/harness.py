"""One run of one cell: resolve it from ``BENCHMARK.json``, set it up, time
a closed loop of jobs, and check what the timed jobs produced.

A cell names a configuration (``bench/configs/<config>.json``, whose
``app`` names ``bench/apps/<app>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``: ``mode``, the ``ExecutionPlan`` method
that builds the job, and ``job``, its ``JobConfig`` fields).  A cell on
several chips runs a mode that takes a mesh (``sharded``).  Each
per-layer metric is read by ``bench/metrics/<metric>.py``.  So a new cell,
deployment, mix or metric is new files and ``BENCHMARK.json`` entries.

Every cell is one client in a closed loop: it submits the cell's job on
the input already resident on the device, waits for the whole output,
releases it, and submits the next, while less than ``seconds`` have
passed since the first submit.  The last job's output is compared in full
with the reference; every job's output is compared with that one by a
fingerprint computed on the device.
"""

from __future__ import annotations

import functools
import importlib.util
import inspect
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import check
from bench import scopes as sc
from bench import trace as tr

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GIB = float(1 << 30)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    app: object
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def load_module(path: Path):
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, app and metrics."""
    manifest = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    return cell_from(cells[name], manifest, root)


def cell_from(w: dict, manifest: dict, root: Path = ROOT) -> Cell:
    """The cell of one ``workloads`` entry ``w`` of ``manifest``."""
    name = w["name"]
    entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = read_json(root / entry["file"])
    traffic = read_json(root / f"bench/traffic/{w['traffic']}.json")
    app = load_module(root / f"bench/apps/{config['app']}.py")

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name, int(w["chips"]), config, traffic, app,
                [m for m in manifest["end_to_end"] if applies(m)],
                [m for m in manifest["per_layer"] if applies(m)])


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where the environment sets it, else the fixed ``<checkout>/.jax_cache``
    (the path is part of the cache's key, so it never moves)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    """A PRNG key from any whole number (all 64 bits of it count)."""
    import jax

    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def job_config(cell: Cell):
    """The configuration's engine settings and the traffic's job fields;
    ``JobConfig`` refuses a key it does not know, or one given twice."""
    from repro.mapreduce import JobConfig

    return JobConfig(**cell.config["engine"], **cell.traffic["job"])


def takes_mesh(mode) -> bool:
    """Whether an ``ExecutionPlan`` mode runs on a device mesh."""
    return "mesh" in inspect.signature(mode).parameters


def mode_job(cell: Cell, plan, sharding, **kwargs):
    """The traffic's ``mode`` of ``plan`` (with ``kwargs``), on the mesh of
    ``sharding`` where the mode takes one."""
    from jax.sharding import NamedSharding

    mode = getattr(plan, cell.traffic["mode"])
    if isinstance(sharding, NamedSharding):
        return mode(sharding.mesh, **kwargs)
    return mode(**kwargs)


def build_entry(cell: Cell, devices):
    """The program's plan, the jitted job the window drives (the plan's
    ``mode`` method), and where its input lives.

    A mode that takes a mesh gets a 1-D ``workers`` mesh over the cell's
    first ``chips`` devices, and its input is laid out in contiguous
    blocks, one per chip, as HDFS leaves each node its own blocks of the
    log.  The mesh's axis is ``Auto``: on an ``Explicit`` axis (the
    default of ``jax.make_mesh``) ``sharded`` cannot take an input split
    over the chips, as the update that pads it into the map tasks' splits
    has no output sharding it can resolve.  Any other mode runs on one
    chip, with its input whole there."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    from repro.mapreduce import ExecutionPlan

    cell.app.validate(cell.config, cell.traffic["job"])
    cfg = job_config(cell)
    plan = ExecutionPlan(cell.app.make_app(cell.config), cfg,
                         cell.config["tokens"])
    mode = cell.traffic["mode"]
    if not takes_mesh(getattr(plan, mode)):
        if cell.chips > 1:
            raise ValueError(f"{cell.name}: mode {mode!r} takes no mesh, "
                             f"so it cannot run on {cell.chips} chips")
        sharding = SingleDeviceSharding(devices[0])
    elif cfg.num_workers != cell.chips:
        raise ValueError(f"{cell.name}: num_workers={cfg.num_workers}, but "
                         f"the cell has {cell.chips} chips")
    elif cell.config["tokens"] % cell.chips:
        raise ValueError(f"{cell.name}: tokens={cell.config['tokens']} do "
                         f"not split evenly over {cell.chips} chips")
    else:
        mesh = jax.make_mesh((cell.chips,), ("workers",),
                             axis_types=(jax.sharding.AxisType.Auto,),
                             devices=devices[: cell.chips])
        sharding = NamedSharding(mesh, PartitionSpec("workers"))
    return plan, mode_job(cell, plan, sharding), sharding


def closed_loop(job, tokens, seconds: float, fingerprint):
    """Submit, wait, release, until ``seconds`` have passed since the first
    submit; at least one job.  Returns the last output (kept for the
    check), the device fingerprints and drop counts of every job, the
    start and each completion time."""
    import jax
    from jax.profiler import TraceAnnotation

    prints, drops, done = [], [], []
    t0 = time.perf_counter()
    while True:
        with TraceAnnotation("bench.submit"):
            out = job(tokens)
        with TraceAnnotation("bench.wait"):
            jax.block_until_ready(out)
        done.append(time.perf_counter())
        with TraceAnnotation("bench.release"):
            prints.append(fingerprint(out[0], out[1]))
            drops.append(out[2])
            if done[-1] - t0 >= seconds:
                break
            del out
    return out, prints, drops, t0, done


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


@dataclass
class Readings:
    """What a per-layer metric's reader (``bench/metrics/<name>.py``,
    ``read(readings) -> float | None``) reads from."""

    window: tr.Trace
    jobs: int
    window_s: float
    devices: list
    #: device ms per job of each phase of the timed job, read from its own
    #: ``mr.<phase>`` scopes and averaged over the chips used
    #: (:func:`bench.scopes.mean_scope_ms`); empty where the trace holds
    #: no run of the job
    scopes: dict = field(default_factory=dict)
    #: the device counters of one run of the mode's ``counters=True``
    #: variant after the window, or None
    counters: dict | None = None

    @property
    def busy_s(self) -> float:
        return tr.mean_busy_s(self.window, self.devices)


def _profiled(directory: str, fn, *args):
    """``fn(*args)`` under the profiler, without its Python tracer (the
    benchmark's ``TraceAnnotation`` spans stay) and without the programs'
    HLO protos (the job's text is taken from the compiled job)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        return fn(*args)
    finally:
        jax.profiler.stop_trace()


def _load_trace(directory: str) -> tr.Trace:
    paths = sorted(Path(directory).glob("**/*.xplane.pb"))
    return tr.load(paths[-1]) if paths else tr.Trace()


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, save: str | None = None) -> dict:
    """One run of ``cell`` on ``devices`` (the first ``cell.chips`` are
    used); ``t_start`` is the process's start on ``time.perf_counter``.
    Returns the result line's fields and, under ``_log``, what the run
    prints on standard error.  With ``save``, a traced run keeps its
    window's ``.xplane.pb`` and the job's HLO text (``job.hlo.txt``,
    without source locations) in that directory."""
    import jax

    if trace:
        # The job's scopes are read from its compiled text, so the traced
        # run keys its programs by their metadata too: an executable
        # compiled from a program that differs only in its scopes is
        # never served in its place.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    used = list(devices[: cell.chips])
    used_ids = [d.id for d in used]
    setup, log = {}, []
    t = time.perf_counter()
    plan, job, sharding = build_entry(cell, used)
    gen = jax.jit(functools.partial(cell.app.generate, cell.config),
                  out_shardings=sharding)
    tokens = jax.block_until_ready(gen(seed_key(seed)))
    setup["generate_s"] = time.perf_counter() - t
    # The generator's own high-water mark, so that the run's peak can be
    # told apart from it.
    memory = {"generate_peak_bytes": peak_bytes(used)}

    t = time.perf_counter()
    compiled = job.lower(tokens).compile()
    setup["compile_s"] = time.perf_counter() - t
    analysis = compiled.memory_analysis()
    if analysis is not None:
        memory["job_temp_bytes"] = analysis.temp_size_in_bytes
        memory["job_output_bytes"] = analysis.output_size_in_bytes

    t = time.perf_counter()
    fingerprint = jax.jit(check.fingerprint)
    out = jax.block_until_ready(compiled(tokens))
    jax.block_until_ready(fingerprint(out[0], out[1]))
    del out
    setup["warmup_s"] = time.perf_counter() - t

    tdir = None
    if trace:
        hlo = compiled.as_text()
        scopes, module = sc.scope_map(hlo), sc.module_of(hlo)
        tdir = save or tempfile.mkdtemp(prefix="bench-trace-")
        if save:
            Path(save).mkdir(parents=True, exist_ok=True)
            (Path(save) / "job.hlo.txt").write_text(sc.without_sources(hlo))
    try:
        setup_s = time.perf_counter() - t_start
        loop = functools.partial(closed_loop, compiled, tokens, seconds,
                                 fingerprint)
        if trace:
            out, prints, drops, t0, done = _profiled(f"{tdir}/window", loop)
        else:
            out, prints, drops, t0, done = loop()
        jobs, window_s = len(done), done[-1] - t0
        memory_peak = memory["peak_bytes"] = peak_bytes(used)
        allocator = max((d.memory_stats() or {} for d in used),
                        key=lambda m: m.get("peak_bytes_in_use", 0))

        # The check: copy the last output out, free the device, then run
        # the reference on the host.
        t = time.perf_counter()
        out_keys, out_vals = np.asarray(out[0]), np.asarray(out[1])
        del out
        prints = [int(p) for p in prints]
        drops = [int(d) for d in drops]
        host_tokens = np.asarray(tokens)
        check_s = time.perf_counter() - t
        counters = None
        if trace:
            # The phase-boundary counters: one run of the mode's counting
            # variant, after the window and the peak's reading.
            stats = mode_job(cell, plan, sharding, counters=True)(tokens)[-1]
            counters = {k: int(v) for k, v in jax.device_get(stats).items()
                        if np.ndim(v) == 0}
        del loop, tokens, compiled, job, plan
        t = time.perf_counter()
        keys, vals = cell.app.pairs(np, host_tokens, cell.config)
        ref_counts, ref_sums = check.exact(keys, vals,
                                           cell.config["key_space"])
        wrong = check.wrong_keys(out_keys, out_vals, ref_counts, ref_sums)
        check_s += time.perf_counter() - t

        readings = None
        if trace:
            window = _load_trace(f"{tdir}/window")
            readings = Readings(
                window, jobs, window_s, used_ids,
                sc.mean_scope_ms(window, used_ids, module, scopes), counters)
            busy = [sc.busy_ms(window, d, module) for d in used_ids]
    finally:
        if tdir and not save:
            shutil.rmtree(tdir, ignore_errors=True)

    compared = {
        "wrong_keys": wrong,
        "dropped": max(drops),
        "jobs_differing": sum(p != prints[-1] for p in prints),
    }
    correct = all(v <= check.LIMITS[k] for k, v in compared.items())
    last_bad = wrong > 0 or drops[-1] > 0
    failed = sum(last_bad or p != prints[-1] or d > 0
                 for p, d in zip(prints, drops))

    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    values = {"job_s": window_s / jobs, "setup_s": setup_s,
              "peak_hbm_gib": memory_peak / GIB}
    metrics, result = {}, {}
    if readings is None:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = readings.busy_s
        device["window_s"] = window_s
        top = max(used_ids, key=lambda d: tr.busy_ns(readings.window, d))
        result["breakdown"] = {
            "device_ops": tr.top_ops(readings.window, top),
            "idle_gaps": tr.idle_gaps(readings.window, top),
        }

    log.append("setup " + " ".join(f"{k}={v:.3f}" for k, v in setup.items())
               + f" total_s={setup_s:.3f}")
    log.append("memory " + " ".join(f"{k}={v}" for k, v in memory.items()))
    log.append("allocator " + " ".join(f"{k}={v}"
                                       for k, v in sorted(allocator.items())))
    if readings is not None:
        log.append(f"scopes module={module} " + " ".join(
            f"{p}_ms={v}" for p, v in readings.scopes.items())
            + f" sum_ms={sum(readings.scopes.values())} module_busy_ms="
            + ",".join(str(b) for b in busy))
        log.append("counters " + " ".join(
            f"{k}={v}" for k, v in sorted((counters or {}).items())))
    log.append(f"window jobs={jobs} window_s={window_s:.4f} "
               f"check_s={check_s:.3f} failed={failed}")
    log += [f"compared {k}={v} limit={check.LIMITS[k]}"
            for k, v in compared.items()]
    return {"correct": correct, "attempted": jobs, "failed": failed,
            "metrics": metrics, "device": device, **result,
            "compared": {k: {"value": v, "limit": check.LIMITS[k]}
                         for k, v in compared.items()},
            "_log": log}
