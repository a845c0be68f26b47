"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with checkpointing, failure injection + automatic restore, and step-time
profiling feeding the paper's config->time model.

    PYTHONPATH=src python examples/train_lm.py              # full (~100M)
    PYTHONPATH=src python examples/train_lm.py --tiny       # CI-sized

After training, the collected per-step wall times are fit against the
microbatch-count knob — the paper's profiling->modeling loop applied to the
trainer itself.
"""

import argparse
import dataclasses
import shutil

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import fit
from repro.data import DataConfig
from repro.launch.train import TrainLoopConfig, run_training
from repro.train import StepConfig


def model_100m() -> ModelConfig:
    """~100M params: 12L d=768 12H GQA kv=4, llama-style."""
    return ModelConfig(
        name="repro-100m", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv_heads=4, head_dim=64, d_ff=2048,
        vocab_size=32000, ffn_type="swiglu", rope_theta=10000.0,
    )


def model_tiny() -> ModelConfig:
    return dataclasses.replace(
        model_100m(), name="repro-tiny", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a node failure at this step (demo)")
    args = ap.parse_args()
    cfg = model_tiny() if args.tiny else model_100m()
    steps = args.steps or (60 if args.tiny else 300)
    batch = args.batch or (8 if args.tiny else 16)
    seq = args.seq or (64 if args.tiny else 512)
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch, structure=0.9)
    print(f"training {cfg.name} for {steps} steps "
          f"(batch {batch} x seq {seq})")
    out = run_training(
        cfg, data,
        TrainLoopConfig(steps=steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=max(10, steps // 10),
                        fail_at_step=args.fail_at, lr=1e-3),
        StepConfig(remat="none"),
    )
    losses = out["losses"]
    print(f"\nloss: {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({len(losses)} recorded steps)")
    assert losses[-1] < losses[0], "training should reduce loss"

    # --- paper technique on the trainer itself: model step time vs the
    # microbatch knob, predict an unprofiled setting -----------------------
    from repro.train import build_train_step
    import jax, time
    from repro.models import transformer as tf
    from repro.optim import adamw

    knob_values = [1, 2, 4, 8]
    times, params_rows = [], []
    pipeline_batch = data
    for mb in knob_values:
        step = jax.jit(build_train_step(
            cfg, adamw.AdamWConfig(lr=1e-3), StepConfig(microbatch=mb)
        ), donate_argnums=(0, 1))
        p = tf.init_params(cfg, jax.random.PRNGKey(0))
        s = adamw.init_state(adamw.AdamWConfig(lr=1e-3), p)
        from repro.data import TokenPipeline
        b = TokenPipeline(pipeline_batch).batch_at(0)
        p, s, m = step(p, s, b)  # compile+warm
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            p, s, m = step(p, s, b)
            jax.block_until_ready(m["loss"])
            reps.append(time.perf_counter() - t0)
        times.append(float(np.mean(reps)))
        params_rows.append([mb])
        print(f"microbatch={mb}: {times[-1] * 1e3:.1f}ms/step")
    model = fit(np.asarray(params_rows, float), np.asarray(times),
                degree=2, scale=True, lam=1e-9)
    pred3 = float(np.asarray(model.predict(np.array([3.0]))).ravel()[0])
    print(f"predicted step time at unprofiled microbatch=3: "
          f"{pred3 * 1e3:.1f}ms")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
