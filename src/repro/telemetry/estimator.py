"""Static per-phase flops/bytes estimates from XLA's cost analysis.

Wall-clock telemetry (``trace.py``) answers "what did this run cost"; this
module answers "what does XLA *think* each phase costs" — without running
anything.  Each phase function of the canonical
:class:`repro.mapreduce.plan.ExecutionPlan` (the same stepper loops every
execution mode runs) is lowered and compiled for abstract (shape-only)
inputs, and the compiled executable's cost analysis (flops, bytes
accessed) is read by :func:`compiled_cost_analysis`.

The estimates feed two consumers:

* the ``phases`` benchmark section reports them next to measured wall
  times, giving a roofline-style sanity check per phase;
* arithmetic-intensity ratios (flops/byte) distinguish compute-bound
  phases (map's per-task setup matmuls) from memory/sort-bound ones
  (shuffle), which is the qualitative split the paper's companion CPU- and
  network-modeling papers draw.

Cost analysis availability varies by backend/jax version; estimates carry
an ``available`` flag and all consumers degrade gracefully.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.mapreduce.phases import PAIR_BYTES
from repro.mapreduce.plan import ExecutionPlan

#: cost_analysis key for bytes moved (XLA's name, with fallbacks).
_BYTES_KEYS = ("bytes accessed", "bytes_accessed")


def compiled_cost_analysis(fn, *abstract_args) -> dict:
    """Lower + compile ``fn`` for abstract (shape/dtype-only) arguments and
    return its XLA cost analysis as a dict.

    Returns ``{}`` when the backend provides no cost analysis or compiling
    the probe fails — callers treat an empty dict as "estimates
    unavailable", never as an error (telemetry must not take the engine
    down).
    """
    try:
        compiled = jax.jit(fn).lower(*abstract_args).compile()
        return dict(compiled.cost_analysis() or {})
    except Exception:  # pragma: no cover - backend dependent
        return {}


def _pick(cost: dict, *keys, default: float = 0.0) -> float:
    for k in keys:
        if k in cost:
            return float(cost[k])
    return default


def stage_cost_estimates(app, cfg, input_len: int) -> dict[str, dict]:
    """Per-phase {flops, bytes, flops_per_byte, available} via XLA, plus
    static resource estimates (``cpu_flops``, ``net_bytes``).

    Phases are the plan's compute stages (map, shuffle, reduce); collect
    is host-side and has no XLA program.  ``available=False`` (with zeroed
    numbers) means the backend reported no cost model for that stage.

    ``cpu_flops`` mirrors the XLA flop count (everything the lowered
    program executes runs on host CPU cores here); ``net_bytes`` is the
    shape-derived fabric upper bound — the shuffle's pair-slot capacity
    times the wire pair size, zero for the compute phases.  It pairs with
    the *measured* ``net_bytes`` trace counter (actual emitted pairs) the
    way ``bytes`` pairs with measured wall times.
    """
    plan = ExecutionPlan(app, cfg, input_len)
    stages = plan.phase_fns()
    meta = plan.meta()
    i32 = jnp.int32
    tok = jax.ShapeDtypeStruct((input_len,), i32)
    acc = jax.ShapeDtypeStruct((plan.M, plan.P), i32)
    acc_b = jax.ShapeDtypeStruct((plan.M, plan.P), jnp.bool_)
    part = jax.ShapeDtypeStruct(
        (plan.R, meta["partition_capacity"]), i32
    )
    abstract_args = {
        "map": (tok,),
        "shuffle": (acc, acc, acc_b),
        "reduce": (part, part),
    }
    out: dict[str, dict] = {}
    for phase, fn in stages.items():
        cost = compiled_cost_analysis(fn, *abstract_args[phase])
        flops = _pick(cost, "flops")
        nbytes = _pick(cost, *_BYTES_KEYS)
        out[phase] = {
            "flops": flops,
            "bytes": nbytes,
            "flops_per_byte": flops / nbytes if nbytes > 0 else 0.0,
            "available": bool(cost),
            "cpu_flops": flops,
            "net_bytes": (
                float(meta["n_pairs"] * PAIR_BYTES)
                if phase == "shuffle" else 0.0
            ),
        }
    return out


def estimates_available(estimates: dict[str, dict]) -> bool:
    """True when at least one phase reported a real XLA cost model."""
    return any(e.get("available") for e in estimates.values())
