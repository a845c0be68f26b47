"""Batched serving example: prefill + decode loop with a KV cache, plus the
paper's model predicting decode latency as a function of batch size (the
serving-side scheduling use case from the paper's conclusion).

    PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.core import fit
from repro.models import transformer as tf
from repro.train import StepConfig, build_decode_step


def main() -> None:
    cfg = smoke_config("llama3-8b")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    decode = jax.jit(build_decode_step(cfg, StepConfig()),
                     donate_argnums=(1,))
    max_len = 128

    def serve_batch(batch_size: int, prompt_len: int = 16,
                    new_tokens: int = 32, time_it: bool = False):
        key = jax.random.PRNGKey(batch_size)
        prompts = jax.random.randint(
            key, (batch_size, prompt_len), 0, cfg.vocab_size, jnp.int32)
        state = tf.init_decode_state(cfg, batch_size, max_len)
        logits, state = decode(params, state, {"tokens": prompts})
        toks = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        outs = [toks]
        t0 = time.perf_counter()
        for _ in range(new_tokens):
            logits, state = decode(params, state, {"tokens": toks})
            toks = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            outs.append(toks)
        jax.block_until_ready(toks)
        dt = time.perf_counter() - t0
        return jnp.concatenate(outs, 1), dt / new_tokens

    # warm + serve a batch
    generated, per_tok = serve_batch(4)
    print(f"served batch of 4, 32 new tokens, "
          f"{per_tok * 1e3:.2f}ms/token: sample {generated[0][:8].tolist()}")

    # paper technique: decode-latency model over the batch-size knob
    sizes, times = [], []
    for b in (1, 2, 4, 8):
        serve_batch(b, new_tokens=4)  # compile for this shape
        _, t = serve_batch(b, new_tokens=16)
        sizes.append([b])
        times.append(t)
        print(f"batch={b}: {t * 1e3:.2f}ms/token")
    model = fit(np.asarray(sizes, float), np.asarray(times),
                degree=2, scale=True, lam=1e-9)
    pred6 = float(np.asarray(model.predict(np.array([6.0]))).ravel()[0])
    print(f"predicted ms/token at unprofiled batch=6: {pred6 * 1e3:.2f}ms "
          f"-> a scheduler can now pick batch size against an SLO")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
