"""Per-phase device time of the timed job itself, from the job's own named
scopes, and the live share of the slots its shuffle and reduce walk.

The program runs every phase under ``jax.named_scope("mr.<phase>")``
(``repro.mapreduce.phases.scoped``), so each HLO instruction of a compiled
job carries ``mr.<phase>`` in its ``op_name`` metadata.  A TPU trace's op
events carry only the instruction's text, without that metadata, so the
phase of an op comes from the compiled program's text
(``compiled.as_text()``): :func:`scope_map`.  :func:`scope_ms` then reduces
a trace (``bench.trace.Trace``) to device ms per phase per job, counting
only ops inside the runs of the job's own module.

Run as a script, it measures one cell on the chip the way a ``--trace 1``
run would read these numbers (the benchmark's harness does not read them
yet), and prints one JSON line:

    python3 bench/scopes.py --workload exim-mainlog.m20r5 --seed 7 \\
        --seconds 45

It times a closed loop of the cell's job untraced, then the same loop
under the profiler, reduces the traced loop to ms per scope, and runs the
job's ``counters=True`` variant once for the live-slot shares.  With
``--tokens N`` the cell's input is cut to ``N`` tokens; ``--save DIR``
keeps the traced loop's ``.xplane.pb`` and the job's HLO text there, and
traces without the Python tracer and the HLO protos to keep them small.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace as tr  # noqa: E402

#: the program's scope prefix (``repro.mapreduce.phases.SCOPE``)
PREFIX = "mr"
#: the phase of an instruction that no scope reaches
UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_CALLEES = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")


def module_of(hlo_text: str) -> str:
    """The compiled program's module name (``HloModule jit_job, ...`` ->
    ``jit_job``), which its runs carry in a trace's ``XLA Modules``."""
    m = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    return m.group(1) if m else ""


def scope_map(hlo_text: str, prefix: str = PREFIX) -> dict[str, str]:
    """Each instruction name of a compiled HLO module -> its phase.

    An instruction's phase is the last ``<prefix>.<phase>`` component of
    its ``op_name`` metadata.  One without such a scope takes the phase of
    the instruction that calls its computation (a loop body, a fusion, a
    comparator).  An instruction that a compiler pass made, whose
    ``op_name`` is missing or is not a path of the traced program
    (``jit(<fn>)/...``), and whose caller has no phase either, takes the
    phase of its first operand that has one, else of its first user.  An
    instruction that none of these reaches is ``unscoped``."""
    scope = re.compile(rf"(?:^|/){re.escape(prefix)}\.(\w+)(?=/|$)")
    own: dict[str, str | None] = {}
    made: set[str] = set()             # instructions a compiler pass made
    insts: dict[str, list[str]] = {}   # computation -> its instructions
    calls: dict[str, list[str]] = {}   # instruction -> computations
    refs: dict[str, list[str]] = {}    # instruction -> names it mentions
    entry, comp = None, None
    for line in hlo_text.splitlines():
        inst = _INSTRUCTION.match(line)
        if inst and comp is not None:
            name = inst.group(1)
            insts[comp].append(name)
            meta = _OP_NAME.search(line)
            phases = scope.findall(meta.group(1)) if meta else []
            own[name] = phases[-1] if phases else None
            if not (meta and meta.group(1).startswith("jit(")):
                made.add(name)
            callees = _CALLEE.findall(line)
            for group in _CALLEES.findall(line):
                callees += [c.strip().lstrip("%") for c in group.split(",")]
            calls[name] = [c for c in callees if c]
            rhs = line.split(" = ", 1)[1].split(", metadata=", 1)[0]
            refs[name] = _REF.findall(rhs)
            continue
        head = _COMPUTATION.match(line)
        if head and not line[:1].isspace():
            comp = head.group(1)
            insts[comp] = []
            if line.startswith("ENTRY"):
                entry = comp
    phase_of: dict[str, str] = {}
    stack = [(entry, UNSCOPED)] if entry in insts else []
    seen = set()
    while stack:
        comp, inherited = stack.pop()
        if comp in seen or comp not in insts:
            continue
        seen.add(comp)
        names = insts[comp]
        local = set(names)
        got = {n: own[n] or (inherited if inherited != UNSCOPED else None)
               for n in names}
        operands = {n: [r for r in refs[n] if r in local] for n in names}
        users: dict[str, list[str]] = {n: [] for n in names}
        for n in names:
            for o in operands[n]:
                users[o].append(n)
        changed = True
        while changed:
            changed = False
            for n in names:
                if got[n] is None and n in made:
                    near = [got[o] for o in operands[n]] + [
                        got[u] for u in users[n]]
                    got[n] = next((p for p in near if p), None)
                    changed |= got[n] is not None
        for n in names:
            phase_of[n] = got[n] or UNSCOPED
            stack += [(c, phase_of[n]) for c in calls[n]]
    return phase_of


def instruction(text: str) -> str:
    """An op event's instruction name: ``%sort.6 = (...) sort(...)`` ->
    ``sort.6``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def job_ops(trace: tr.Trace, device: int, module: str):
    """The ops of one device that ran inside a run of ``module``, and the
    number of its runs."""
    runs = sorted((e for e in trace.modules.get(device, [])
                   if tr.module_name(e.name) == module),
                  key=lambda e: e.start_ns)
    starts = [e.start_ns for e in runs]
    ops = []
    for e in trace.ops.get(device, []):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < runs[i].end_ns:
            ops.append(e)
    return ops, len(runs)


def busy_ms(trace: tr.Trace, device: int, module: str) -> float | None:
    """Device ms per run of ``module`` in which some op of it ran."""
    ops, n = job_ops(trace, device, module)
    if not n:
        return None
    return sum(b - a for a, b in tr.union(ops)) / n / 1e6


def scope_ms(trace: tr.Trace, device: int, module: str,
             scopes: dict[str, str]) -> dict[str, float]:
    """Device ms per phase per run of ``module``: for each phase of
    ``scopes`` (:func:`scope_map`), the union of the intervals of its ops,
    counting only ops inside the module's runs (so another program's ops,
    such as the benchmark's fingerprint between jobs, are left out), over
    the number of runs.  Ops not in ``scopes`` count as ``unscoped``."""
    ops, n = job_ops(trace, device, module)
    if not n:
        return {}
    groups: dict[str, list] = {}
    for e in ops:
        groups.setdefault(scopes.get(instruction(e.name), UNSCOPED),
                          []).append(e)
    return {p: sum(b - a for a, b in tr.union(evs)) / n / 1e6
            for p, evs in sorted(groups.items())}


def live_pct(counters: dict) -> dict[str, float]:
    """The shuffle's and the reduce's live pairs as a share of the slots
    they walk, from a ``counters=True`` job's stats."""
    return {
        f"{phase}_live_pct":
            100.0 * counters[f"{phase}.pairs"] / counters[f"{phase}.slots"]
        for phase in ("shuffle", "reduce")
        if counters.get(f"{phase}.slots")
    }


# ------------------------------------------------------------------ script


def without_sources(hlo_text: str) -> str:
    """The HLO text without its source-location tables and the source
    file, line and stack-frame fields of its metadata, which
    :func:`scope_map` does not read."""
    lines = [line for line in hlo_text.splitlines()
             if line[:1].isspace() or not (
                 line[:1].isdigit() or line in (
                     "FileNames", "FunctionNames", "FileLocations",
                     "StackFrames"))]
    text = "\n".join(lines) + "\n"
    return re.sub(r" (?:source_\w+|stack_frame_id)=(?:\"[^\"]*\"|\d+)", "",
                  text)


def measure(cell, seed: int, seconds: float, devices, save: str | None):
    """The numbers of one run; see the module's docstring."""
    import functools
    import shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from bench import check, harness

    used = list(devices[: cell.chips])
    ids = [d.id for d in used]
    plan, job, sharding = harness.build_entry(cell, used)
    gen = jax.jit(functools.partial(cell.app.generate, cell.config),
                  out_shardings=sharding)
    tokens = jax.block_until_ready(gen(harness.seed_key(seed)))

    # The job and its counting variant, compiled side by side.
    counted = getattr(plan, cell.traffic["mode"])(counters=True)
    t = time.perf_counter()
    with ThreadPoolExecutor(max_workers=2) as pool:
        compiled, counted = pool.map(
            lambda p: p.lower(tokens).compile(), (job, counted))
    compile_s = time.perf_counter() - t
    hlo = compiled.as_text()
    scopes, module = scope_map(hlo), module_of(hlo)

    fingerprint = jax.jit(check.fingerprint)
    out = jax.block_until_ready(compiled(tokens))
    jax.block_until_ready(fingerprint(out[0], out[1]))
    del out
    setup_s = time.perf_counter() - T_START

    loop = functools.partial(harness.closed_loop, compiled, tokens,
                             seconds, fingerprint)
    _, _, _, t0, done = loop()
    untraced = {"jobs": len(done), "job_s": (done[-1] - t0) / len(done)}

    options = jax.profiler.ProfileOptions()
    if save:
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
    tdir = tempfile.mkdtemp(prefix="bench-scopes-")
    try:
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            _, prints, drops, t0, done = loop()
        finally:
            jax.profiler.stop_trace()
        traced = {"jobs": len(done), "job_s": (done[-1] - t0) / len(done)}
        window = harness._load_trace(tdir)
        if save:
            Path(save).mkdir(parents=True, exist_ok=True)
            path = sorted(Path(tdir).glob("**/*.xplane.pb"))[-1]
            shutil.copy(path, Path(save) / "scoped.xplane.pb")
            (Path(save) / "scoped.hlo.txt").write_text(without_sources(hlo))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    ok, ov, _, stats = counted(tokens)
    same = int(fingerprint(ok, ov)) == int(prints[-1])
    counters = {k: int(v) for k, v in jax.device_get(stats).items()}
    del ok, ov

    per_device = [scope_ms(window, d, module, scopes) for d in ids]
    scoped = {p: sum(s.get(p, 0.0) for s in per_device) / len(ids)
              for p in sorted({p for s in per_device for p in s})}
    busy = [busy_ms(window, d, module) for d in ids]
    return {
        "device": {"platform": used[0].platform,
                   "kind": used[0].device_kind, "count": len(devices)},
        "module": module,
        "instructions": len(scopes),
        "instructions_scoped": sum(p != UNSCOPED for p in scopes.values()),
        "untraced": untraced,
        "traced": traced,
        "module_busy_ms": (sum(busy) / len(busy)
                           if None not in busy else None),
        "scope_ms": scoped,
        "scope_sum_ms": sum(scoped.values()),
        "counters": counters,
        "counted_output_same": same,
        "dropped": max(int(d) for d in drops),
        **live_pct(counters),
        "setup": {"compile_both_s": compile_s, "setup_s": setup_s},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tokens", type=int, default=None,
                    help="cut the cell's input to this many tokens")
    ap.add_argument("--save", default=None,
                    help="directory for the traced loop's .xplane.pb and "
                         "the job's HLO text")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.resolve(args.workload)
    if args.tokens:
        cell.config["tokens"] = args.tokens
    harness.enable_compile_cache()
    import jax

    result = measure(cell, args.seed, args.seconds, jax.devices(),
                     args.save)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
