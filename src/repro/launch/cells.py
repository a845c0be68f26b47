"""Dry-run cell construction: (arch x shape x mesh) -> lowered/compiled step.

Shared by ``launch/dryrun.py`` (512-device production meshes) and the smoke
dry-run tests (small meshes).  No jax device state is touched at import.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ModelConfig, SHAPES, get_config, input_specs
from repro.configs.base import ShapeConfig
from repro.core import costmodel
from repro.models import transformer as tf
from repro.optim import adamw
from repro.sharding import rules
from repro.sharding.context import use_mesh
from repro.train import step as step_mod


@dataclasses.dataclass(frozen=True)
class CellConfig:
    """Per-cell runtime knobs (the §Perf hillclimb levers)."""

    remat: str = "full"
    logits_chunk: int = 0
    microbatch: int = 1
    fsdp: bool = False
    unroll_layers: bool = False    # shallow probes set this (see analyze)
    opt_state_dtype: str = "float32"
    master_fp32: bool = False
    cache_dtype: str = "bfloat16"
    moe_n_groups: int | None = None   # override cfg.moe.n_groups


def default_cell_config(cfg: ModelConfig, shape: ShapeConfig) -> CellConfig:
    """Baseline knobs: remat-full for train, FSDP for >16B-total archs."""
    if shape.kind == "train":
        return CellConfig(
            remat="full",
            fsdp=cfg.total_params() * 2 > 32e9,  # bf16 bytes over ~2GB/chip TP
        )
    return CellConfig(remat="none")


def _apply_overrides(cfg: ModelConfig, cell: CellConfig, mesh) -> ModelConfig:
    if cfg.moe is not None:
        # default dispatch groups = number of data shards, so each group is
        # shard-local at the production sharding
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_total = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
        n_groups = cell.moe_n_groups or dp_total
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_groups=n_groups)
        )
    return cfg


def _mesh_axes(mesh) -> rules.MeshAxes:
    names = tuple(mesh.axis_names)
    if "pod" in names:
        return rules.MeshAxes(data=("pod", "data"), model="model")
    return rules.MeshAxes(data=("data",), model="model")


def _sharding(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_cell(arch: str, shape_name: str, mesh, *,
               cell: CellConfig | None = None, cfg: ModelConfig | None = None):
    """Build (jitted_fn, example_args, donate) for one dry-run cell.

    Returns dict with fn/args/meta; caller lowers with
    ``fn.lower(*args)`` (args are ShapeDtypeStructs — no allocation).
    """
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    cell = cell or default_cell_config(cfg, shape)
    cfg = _apply_overrides(cfg, cell, mesh)
    axes = _mesh_axes(mesh)

    step_cfg = step_mod.StepConfig(
        remat=cell.remat,
        logits_chunk=cell.logits_chunk,
        microbatch=cell.microbatch,
        cache_dtype=cell.cache_dtype,
        unroll_layers=cell.unroll_layers,
    )
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    params_shapes = jax.eval_shape(
        partial(tf.init_params, cfg), jax.random.PRNGKey(0)
    )
    param_spec = rules.param_specs(
        params_shapes, axes, fsdp=cell.fsdp, mesh_shape=mesh_shape
    )
    param_sh = _sharding(mesh, param_spec)
    batch_shapes = input_specs(cfg, shape)
    batch_spec = rules.batch_specs(batch_shapes, axes, mesh_shape=mesh_shape)
    batch_sh = _sharding(mesh, batch_spec)

    meta = {
        "arch": cfg.name,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "cell_config": dataclasses.asdict(cell),
        "total_params": cfg.total_params(),
        "active_params": cfg.active_params(),
    }

    if shape.kind == "train":
        optim_cfg = adamw.AdamWConfig(
            state_dtype=cell.opt_state_dtype, master_fp32=cell.master_fp32
        )
        opt_shapes = jax.eval_shape(
            partial(adamw.init_state, optim_cfg), params_shapes
        )
        opt_spec = _opt_specs(opt_shapes, param_spec)
        opt_sh = _sharding(mesh, opt_spec)
        fn = step_mod.build_train_step(cfg, optim_cfg, step_cfg)
        jitted = jax.jit(
            fn,
            in_shardings=(param_sh, opt_sh, batch_sh),
            out_shardings=(param_sh, opt_sh, None),
            donate_argnums=(0, 1),
        )
        args = (params_shapes, opt_shapes, batch_shapes)
        meta["model_flops"] = train_model_flops(cfg, shape)
    elif shape.kind == "prefill":
        fn = step_mod.build_prefill_step(cfg, shape.seq_len, step_cfg)
        jitted = jax.jit(
            fn, in_shardings=(param_sh, batch_sh),
        )
        args = (params_shapes, batch_shapes)
        meta["model_flops"] = serve_model_flops(cfg, shape, prefill=True)
    elif shape.kind == "decode":
        fn = step_mod.build_decode_step(cfg, step_cfg)
        state_shapes = step_mod.decode_state_shapes(
            cfg, shape.global_batch, shape.seq_len, step_cfg
        )
        state_spec = rules.decode_state_specs(
            state_shapes, axes, mesh_shape=mesh_shape
        )
        state_sh = _sharding(mesh, state_spec)
        jitted = jax.jit(
            fn,
            in_shardings=(param_sh, state_sh, batch_sh),
            out_shardings=(None, state_sh),
            donate_argnums=(1,),
        )
        args = (params_shapes, state_shapes, batch_shapes)
        meta["model_flops"] = serve_model_flops(cfg, shape, prefill=False)
    else:
        raise ValueError(shape.kind)
    return {"jitted": jitted, "args": args, "meta": meta}


def _opt_specs(opt_shapes, param_spec):
    """Optimizer state specs mirror the param specs (m, v, master)."""
    spec = {
        "step": P(),
        "m": param_spec,
        "v": param_spec,
    }
    if "master" in opt_shapes:
        spec["master"] = param_spec
    return spec


# ---------------------------------------------------------------------------
# MODEL_FLOPS accounting (global, for the useful-compute ratio)
# ---------------------------------------------------------------------------


def train_model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6 * N_active * tokens (+ attention context flops)."""
    tokens = shape.global_batch * shape.seq_len
    base = 6.0 * cfg.active_params() * tokens
    base += 3.0 * _attention_context_flops(cfg, shape.seq_len, tokens)
    return base


def serve_model_flops(cfg: ModelConfig, shape: ShapeConfig,
                      *, prefill: bool) -> float:
    if prefill:
        tokens = shape.global_batch * shape.seq_len
        return (
            2.0 * cfg.active_params() * tokens
            + _attention_context_flops(cfg, shape.seq_len, tokens)
        )
    tokens = shape.global_batch  # one new token per sequence
    base = 2.0 * cfg.active_params() * tokens
    n_attn = sum(1 for k in cfg.layer_kinds() if k == "attn")
    hd = cfg.resolved_head_dim
    # decode attention: q @ K^T + p @ V over the full cache
    base += tokens * n_attn * cfg.n_heads * hd * shape.seq_len * 2 * 2
    return base


def _attention_context_flops(cfg: ModelConfig, seq: int,
                             tokens: float) -> float:
    """2 * (qk + pv) flops for causal attention over the sequence."""
    n_attn = sum(1 for k in cfg.layer_kinds() if k == "attn")
    hd = cfg.resolved_head_dim
    ctx = seq / 2 if cfg.causal else seq
    return tokens * n_attn * cfg.n_heads * hd * ctx * 2 * 2


def analyze_cell(built, *, n_devices: int, mesh=None):
    """lower + compile + roofline report for one cell."""
    if mesh is not None:
        with use_mesh(mesh):
            lowered = built["jitted"].lower(*built["args"])
    else:
        lowered = built["jitted"].lower(*built["args"])
    compiled = lowered.compile()
    report = costmodel.roofline_from_compiled(
        compiled,
        n_devices=n_devices,
        model_flops=built["meta"]["model_flops"],
    )
    mem = compiled.memory_analysis()
    return {
        "meta": built["meta"],
        "roofline": report.to_dict(),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": (
                mem.argument_size_in_bytes
                + mem.temp_size_in_bytes
                + mem.output_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        },
    }


def estimate_step_time(arch: str, shape_name: str, mesh, *,
                       cell: CellConfig | None = None,
                       cfg: ModelConfig | None = None) -> dict:
    """Cheap step-time estimate: shallow probes + extrapolation only (no
    full-depth compile).  This is the profiler backend for the
    paper's-config->time autotuner over launcher knobs (§Perf-llama3)."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    cell = cell or default_cell_config(cfg, shape)
    n_rep = cfg.n_groups_of_layers
    period = cfg.pattern_period
    n_devices = mesh.devices.size
    probe_cell = dataclasses.replace(cell, unroll_layers=True)
    probes = []
    peak = 0
    for depth_groups in (1, 2):
        cfg_p = dataclasses.replace(cfg, n_layers=depth_groups * period)
        built = build_cell(arch, shape_name, mesh, cell=probe_cell,
                           cfg=cfg_p)
        with use_mesh(mesh):
            lowered = built["jitted"].lower(*built["args"])
        compiled = lowered.compile()
        probes.append(_raw_costs(compiled, n_devices))
        mem = compiled.memory_analysis()
        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    p1, p2 = probes
    tot = {k: p1[k] + (n_rep - 1) * (p2[k] - p1[k])
           for k in ("flops", "bytes", "collective_bytes")}
    compute_s = tot["flops"] / costmodel.PEAK_FLOPS_BF16
    memory_s = tot["bytes"] / costmodel.HBM_BW
    collective_s = tot["collective_bytes"] / costmodel.ICI_BW
    return {
        "step_s": max(compute_s, memory_s) + collective_s,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "probe2_peak_bytes": peak,
    }


def _raw_costs(compiled, n_devices):
    cost = compiled.cost_analysis()
    coll = costmodel.parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(coll.total_bytes),
        "collectives": coll,
    }


def analyze_cell_extrapolated(arch: str, shape_name: str, mesh, *,
                              cell: CellConfig | None = None,
                              cfg: ModelConfig | None = None):
    """Depth-exact roofline via secant extrapolation over layer groups.

    XLA's cost_analysis counts `lax.scan` bodies ONCE regardless of trip
    count, so a scanned L-layer model under-reports compute/bytes/collective
    by ~L x.  Unrolling the full depth is compile-prohibitive at 512 devices.
    Instead we compile two SHALLOW UNROLLED probes — depth = 1 period and
    2 periods — whose cost difference is the exact per-group cost (groups
    are homogeneous), then extrapolate:

        total = probe1 + (n_rep - 1) * (probe2 - probe1)

    The full-depth scan compile still provides memory_analysis (peak HBM is
    reported correctly for scans) and proves the production graph compiles.

    Residual known under-count: sequence-chunk scans INSIDE a block (rwkv
    wkv / mamba ssm inner scans) are still costed once per block; bounded
    at <~6% of block flops for rwkv6-3b, <1% elsewhere (DESIGN.md).
    """
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    cell = cell or default_cell_config(cfg, shape)
    n_rep = cfg.n_groups_of_layers
    period = cfg.pattern_period
    n_devices = mesh.devices.size

    # 1) full-depth scan compile: memory + compile proof
    built_full = build_cell(arch, shape_name, mesh, cell=cell, cfg=cfg)
    with use_mesh(mesh):
        lowered_full = built_full["jitted"].lower(*built_full["args"])
    compiled_full = lowered_full.compile()
    mem = compiled_full.memory_analysis()

    # 2) shallow unrolled probes
    probe_cell = dataclasses.replace(cell, unroll_layers=True)
    probes = []
    for depth_groups in (1, 2):
        cfg_p = dataclasses.replace(cfg, n_layers=depth_groups * period)
        built = build_cell(arch, shape_name, mesh, cell=probe_cell, cfg=cfg_p)
        with use_mesh(mesh):
            lowered_p = built["jitted"].lower(*built["args"])
        probes.append(_raw_costs(lowered_p.compile(), n_devices))

    p1, p2 = probes
    extrap = {
        k: p1[k] + (n_rep - 1) * (p2[k] - p1[k])
        for k in ("flops", "bytes", "collective_bytes")
    }
    coll_by_kind = {
        kind: (
            p1["collectives"].bytes_by_kind[kind]
            + (n_rep - 1) * (
                p2["collectives"].bytes_by_kind[kind]
                - p1["collectives"].bytes_by_kind[kind]
            )
        )
        for kind in p1["collectives"].bytes_by_kind
    }
    model_flops = built_full["meta"]["model_flops"]
    report = costmodel.RooflineReport(
        flops=extrap["flops"],
        hbm_bytes=extrap["bytes"],
        collective_bytes=extrap["collective_bytes"],
        compute_s=extrap["flops"] / costmodel.PEAK_FLOPS_BF16,
        memory_s=extrap["bytes"] / costmodel.HBM_BW,
        collective_s=extrap["collective_bytes"] / costmodel.ICI_BW,
        peak_hbm_bytes=float(
            mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
        ),
        dominant="",
        model_flops=model_flops,
        useful_ratio=(
            model_flops / (extrap["flops"] * n_devices)
            if extrap["flops"] else None
        ),
        n_devices=n_devices,
    )
    terms = {
        "compute": report.compute_s,
        "memory": report.memory_s,
        "collective": report.collective_s,
    }
    report = dataclasses.replace(report, dominant=max(terms, key=terms.get))
    rdict = report.to_dict()
    rdict["collective_bytes_by_kind"] = coll_by_kind
    return {
        "meta": built_full["meta"],
        "roofline": rdict,
        "probe_group_cost": {
            k: p2[k] - p1[k] for k in ("flops", "bytes", "collective_bytes")
        },
        "scan_compile_costs": _raw_costs(compiled_full, n_devices)
        | {"collectives": None},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": (
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes
            ),
        },
    }
