"""Per-phase device time of the timed job itself, from the job's own named
scopes, and the live share of the slots its shuffle and reduce walk.

The program runs every phase under ``jax.named_scope("mr.<phase>")``
(``repro.mapreduce.phases.scoped``), so each HLO instruction of a compiled
job carries ``mr.<phase>`` in its ``op_name`` metadata.  A TPU trace's op
events carry only the instruction's text, without that metadata, so the
phase of an op comes from the compiled program's text
(``compiled.as_text()``): :func:`scope_map`.  :func:`scope_ms` then reduces
a trace (``bench.trace.Trace``) to device ms per phase per job, counting
only ops inside the runs of the job's own module.  A phase's time is the
union of its ops' intervals, async copies' spans included, so the phases
together cover the job's busy time.  The benchmark's ``--trace 1`` run
(``bench.harness.run``) reads the per-layer metrics from these.

Run as a script, it makes one such traced run of a cell, on whatever
devices JAX finds (the CPU too, where the trace holds no device op and
so no scope), and prints its result line:

    python3 bench/scopes.py --workload exim-mainlog.m20r5 --seed 7 \\
        --seconds 45

Standard error carries the run's ``scopes`` line (ms per scope, their
sum, the job module's busy ms on each chip) and its ``counters`` line.
With ``--tokens N`` the cell's input is cut to ``N`` tokens; ``--save
DIR`` keeps the traced window's ``.xplane.pb`` and the job's HLO text
there.
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


def mean_scope_ms(trace: tr.Trace, devices, module: str,
                  scopes: dict[str, str]) -> dict[str, float]:
    """:func:`scope_ms` averaged over ``devices``: a phase that a device
    did not run counts 0 there."""
    per_device = [scope_ms(trace, d, module, scopes) for d in devices]
    return {p: sum(s.get(p, 0.0) for s in per_device) / len(per_device)
            for p in sorted({p for s in per_device for p in s})}


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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tokens", type=int, default=None,
                    help="cut the cell's input to this many tokens")
    ap.add_argument("--save", default=None,
                    help="directory that keeps the traced window's "
                         ".xplane.pb and the job's HLO text")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.resolve(args.workload)
    if args.tokens:
        cell.config["tokens"] = args.tokens
    harness.enable_compile_cache()
    import jax

    result = harness.run(cell, args.seed, args.seconds, True, jax.devices(),
                         T_START, save=args.save)
    for line in result.pop("_log"):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
