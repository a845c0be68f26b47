"""Train / serve step builders: remat, microbatching, chunked loss, ZeRO.

``build_train_step`` returns a pure function suitable for
``jax.jit(..., in_shardings=..., donate_argnums=...)`` — the launcher and the
dry-run both consume it.  Distribution is pjit-style: parameter/batch
PartitionSpecs come from ``repro.sharding.rules``; FSDP param sharding makes
XLA emit the all-gather-params / reduce-scatter-grads (ZeRO-3) schedule
automatically.

``build_compressed_dp_train_step`` is the explicit shard_map variant with
int8 error-feedback gradient compression on the DP all-reduce (DP-only,
params replicated) — the distributed-optimization trick from DESIGN.md §3,
measured in §Perf.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.optim import adamw
from repro.optim import grad_compress


@dataclasses.dataclass(frozen=True)
class StepConfig:
    remat: str = "none"            # none | dots | full
    logits_chunk: int = 0          # 0 = full logits
    microbatch: int = 1            # gradient-accumulation chunks
    use_flash: bool = False
    cache_dtype: str = "bfloat16"  # KV cache / SSM state dtype
    unroll_layers: bool = False    # dry-run flop accounting (see transformer)


def build_train_step(cfg: ModelConfig, optim_cfg: adamw.AdamWConfig,
                     step_cfg: StepConfig = StepConfig()):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def loss_fn(p, b):
        return tf.loss_fn(
            p, cfg, b,
            use_flash=step_cfg.use_flash,
            remat=step_cfg.remat,
            logits_chunk=step_cfg.logits_chunk,
            unroll_layers=step_cfg.unroll_layers,
        )

    def grads_of(params, batch):
        if step_cfg.microbatch <= 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        k = step_cfg.microbatch

        def slice_mb(leaf):
            b = leaf.shape[0]
            if b % k:
                raise ValueError(f"batch {b} not divisible by microbatch {k}")
            return leaf.reshape(k, b // k, *leaf.shape[1:])

        mbs = jax.tree.map(slice_mb, batch)

        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        if step_cfg.unroll_layers:
            # dry-run accounting mode: scan bodies are costed once by XLA
            # cost_analysis, so unroll the accumulation loop too
            loss_sum, g_sum = jnp.float32(0.0), g0
            for i in range(k):
                mb = jax.tree.map(lambda l: l[i], mbs)
                loss, g = jax.value_and_grad(loss_fn)(params, mb)
                g_sum = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), g_sum, g
                )
                loss_sum = loss_sum + loss
        else:
            def acc(carry, mb):
                loss_acc, g_acc = carry
                loss, g = jax.value_and_grad(loss_fn)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), g_acc, g
                )
                return (loss_acc + loss, g_acc), None

            (loss_sum, g_sum), _ = jax.lax.scan(
                acc, (jnp.float32(0.0), g0), mbs
            )
        grads = jax.tree.map(lambda g: g / k, g_sum)
        return loss_sum / k, grads

    def train_step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        lr_scale = adamw.cosine_schedule(opt_state["step"])
        params, opt_state, metrics = adamw.apply_updates(
            optim_cfg, params, grads, opt_state, lr_scale
        )
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def build_eval_step(cfg: ModelConfig, step_cfg: StepConfig = StepConfig()):
    def eval_step(params, batch):
        return tf.loss_fn(
            params, cfg, batch,
            use_flash=step_cfg.use_flash,
            logits_chunk=step_cfg.logits_chunk,
        )

    return eval_step


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, max_len: int,
                       step_cfg: StepConfig = StepConfig()):
    """Prompt processing.  Encoder archs: plain forward (no cache)."""
    cache_dtype = jnp.dtype(step_cfg.cache_dtype)
    if not cfg.causal:

        def encode_step(params, batch):
            logits, _ = tf.forward(
                params, cfg, batch, use_flash=step_cfg.use_flash,
                unroll_layers=step_cfg.unroll_layers,
            )
            return logits

        return encode_step

    def prefill_step(params, batch):
        return tf.prefill(
            params, cfg, batch, max_len,
            use_flash=step_cfg.use_flash, cache_dtype=cache_dtype,
            unroll_layers=step_cfg.unroll_layers,
        )

    return prefill_step


def build_decode_step(cfg: ModelConfig,
                      step_cfg: StepConfig = StepConfig()):
    """(params, state, batch(B,1)) -> (logits, state).  State is donated."""

    def decode(params, state, batch):
        return tf.decode_step(
            params, cfg, state, batch, use_flash=step_cfg.use_flash,
            unroll_layers=step_cfg.unroll_layers,
        )

    return decode


def decode_state_shapes(cfg: ModelConfig, batch: int, max_len: int,
                        step_cfg: StepConfig = StepConfig()):
    """ShapeDtypeStruct pytree of the decode state (no allocation)."""
    return jax.eval_shape(
        lambda: tf.init_decode_state(
            cfg, batch, max_len, jnp.dtype(step_cfg.cache_dtype)
        )
    )


# ---------------------------------------------------------------------------
# explicit-DP shard_map step with int8 gradient compression
# ---------------------------------------------------------------------------


def build_compressed_dp_train_step(cfg: ModelConfig,
                                   optim_cfg: adamw.AdamWConfig,
                                   mesh, axis: str = "data",
                                   step_cfg: StepConfig = StepConfig()):
    """DP-only train step: per-shard grads, int8+error-feedback all-reduce.

    params/opt_state replicated; batch sharded on ``axis``.  Returns a step
    taking an extra error-feedback state pytree.
    """
    from jax.sharding import PartitionSpec as P

    def loss_fn(p, b):
        return tf.loss_fn(
            p, cfg, b, use_flash=step_cfg.use_flash,
            remat=step_cfg.remat, logits_chunk=step_cfg.logits_chunk,
        )

    def shard_body(params, opt_state, err_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        n = jax.lax.psum(jnp.float32(1.0), axis)
        grads, err_state = grad_compress.psum_compressed(
            grads, err_state, axis
        )
        grads = jax.tree.map(lambda g: g / n, grads)
        lr_scale = adamw.cosine_schedule(opt_state["step"])
        params, opt_state, metrics = adamw.apply_updates(
            optim_cfg, params, grads, opt_state, lr_scale
        )
        metrics["loss"] = loss
        return params, opt_state, err_state, metrics

    rep = lambda tree: jax.tree.map(lambda _: P(), tree)

    def make(params_like, opt_like, err_like, batch_like):
        batch_spec = jax.tree.map(
            lambda l: P(axis, *([None] * (l.ndim - 1))), batch_like
        )
        return jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(rep(params_like), rep(opt_like), rep(err_like),
                      batch_spec),
            out_specs=(rep(params_like), rep(opt_like), rep(err_like),
                       {"loss": P(), "grad_norm": P(), "lr": P()}),
            check_vma=False,
        )

    return make
