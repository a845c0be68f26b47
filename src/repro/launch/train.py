"""Production training loop: sharded step, checkpoint/restart, failure
retry, elastic resume, step-time profiling hooks.

Usable as a module (``run_training``) or CLI::

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --steps 100 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Fault tolerance model (single-controller JAX):
* every ``ckpt_every`` steps the full train state (params, optimizer, data
  cursor) is checkpointed asynchronously with atomic publish;
* a transient step failure (injected or real) triggers restore-from-latest
  and replay — the data pipeline is stateless-per-step so replay is exact;
* on restart (new process, possibly different device count) the loop
  resumes from LATEST with re-sharding onto the current mesh.

The per-step wall times collected here are exactly the profiling phase of
the paper: ``run_training(..., time_log=...)`` returns them so callers can
fit config->time models over launcher knobs.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import ModelConfig, get_config, smoke_config
from repro.data import DataConfig, TokenPipeline
from repro.models import transformer as tf
from repro.optim import adamw
from repro.sharding import rules
from repro.train import step as step_mod


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    ckpt_every: int = 25
    ckpt_dir: str | None = None
    keep: int = 3
    log_every: int = 10
    seed: int = 0
    lr: float = 3e-4
    fail_at_step: int | None = None   # failure injection (tests/demos)
    max_retries: int = 2


def _make_sharded_step(cfg, optim_cfg, step_cfg, mesh):
    axes = rules.MeshAxes(
        data=tuple(a for a in mesh.axis_names if a != "model")
        or ("data",),
    )
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    params_like = jax.eval_shape(
        lambda k: tf.init_params(cfg, k), jax.random.PRNGKey(0)
    )
    pspec = rules.param_specs(params_like, axes, mesh_shape=mesh_shape)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                       is_leaf=lambda x: isinstance(x, P))
    opt_like = jax.eval_shape(
        lambda p: adamw.init_state(optim_cfg, p), params_like
    )
    ospec = {"step": P(), "m": pspec, "v": pspec}
    if "master" in opt_like:
        ospec["master"] = pspec
    osh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospec,
                       is_leaf=lambda x: isinstance(x, P))
    fn = step_mod.build_train_step(cfg, optim_cfg, step_cfg)
    jitted = jax.jit(fn, donate_argnums=(0, 1))
    return jitted, psh, osh


def run_training(
    cfg: ModelConfig,
    data_cfg: DataConfig,
    loop: TrainLoopConfig = TrainLoopConfig(),
    step_cfg: step_mod.StepConfig = step_mod.StepConfig(),
    optim_cfg: adamw.AdamWConfig | None = None,
    mesh=None,
) -> dict:
    """Returns {"losses": [...], "step_seconds": [...], "last_step": int}."""
    optim_cfg = optim_cfg or adamw.AdamWConfig(lr=loop.lr)
    if mesh is None:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
    jitted, psh, osh = _make_sharded_step(cfg, optim_cfg, step_cfg, mesh)
    pipeline = TokenPipeline(data_cfg)

    mgr = (
        CheckpointManager(loop.ckpt_dir, keep=loop.keep)
        if loop.ckpt_dir else None
    )
    params = tf.init_params(cfg, jax.random.PRNGKey(loop.seed))
    opt_state = adamw.init_state(optim_cfg, params)
    start_step = 0
    if mgr is not None and mgr.latest_step() is not None:
        # elastic resume: restore re-shards onto the *current* mesh
        (params, opt_state), start_step = mgr.restore(
            None, (params, opt_state), shardings=(psh, osh)
        )
        print(f"[train] resumed from checkpoint at step {start_step}")
    else:
        params = jax.device_put(params, psh)
        opt_state = jax.device_put(opt_state, osh)

    losses: list[float] = []
    times: list[float] = []
    injected_failures = {loop.fail_at_step} if loop.fail_at_step else set()
    step = start_step
    retries = 0
    while step < loop.steps:
        batch = pipeline.batch_at(step)
        t0 = time.perf_counter()
        try:
            if step in injected_failures:
                injected_failures.discard(step)
                raise RuntimeError("injected node failure")
            params, opt_state, metrics = jitted(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
        except Exception as e:  # noqa: BLE001 — failure-retry boundary
            retries += 1
            if mgr is None or retries > loop.max_retries:
                raise
            print(f"[train] step {step} failed ({e}); "
                  f"restoring from latest checkpoint")
            mgr.wait()
            params = tf.init_params(cfg, jax.random.PRNGKey(loop.seed))
            opt_state = adamw.init_state(optim_cfg, params)
            if mgr.latest_step() is not None:
                (params, opt_state), step = mgr.restore(
                    None, (params, opt_state), shardings=(psh, osh)
                )
            else:
                step = 0
                params = jax.device_put(params, psh)
                opt_state = jax.device_put(opt_state, osh)
            continue
        dt = time.perf_counter() - t0
        losses.append(float(metrics["loss"]))
        times.append(dt)
        step += 1
        if loop.log_every and step % loop.log_every == 0:
            print(
                f"[train] step {step}/{loop.steps} "
                f"loss={losses[-1]:.4f} "
                f"gnorm={float(metrics['grad_norm']):.3f} "
                f"{dt * 1e3:.0f}ms/step"
            )
        if mgr is not None and step % loop.ckpt_every == 0:
            mgr.save_async(step, (params, opt_state))
    if mgr is not None:
        mgr.wait()
        mgr.save(step, (params, opt_state))
    return {"losses": losses, "step_seconds": times, "last_step": step}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--fail-at", type=int, default=None)
    args = ap.parse_args()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch,
    )
    out = run_training(
        cfg, data_cfg,
        TrainLoopConfig(
            steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, lr=args.lr,
            fail_at_step=args.fail_at,
        ),
    )
    print(
        f"final loss {out['losses'][-1]:.4f} "
        f"(first {out['losses'][0]:.4f}); "
        f"median step {np.median(out['step_seconds']) * 1e3:.0f}ms"
    )


if __name__ == "__main__":
    main()
