"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as a subpackage with kernel.py (pl.pallas_call + explicit
BlockSpec VMEM tiling), ops.py (jit'd public wrapper), and ref.py (pure-jnp
oracle used by the allclose sweep tests).  Tests run them interpreted on
the CPU platform; ``tests/test_tpu_compile.py`` compiles the MapReduce
kernels for a described TPU v5e, the deployment target.  The
dry-run/roofline path deliberately uses the XLA reference implementations
(custom calls hide FLOPs from cost_analysis) — see EXPERIMENTS.md
§Dry-run.
"""

from repro.kernels import (  # noqa: F401
    decode_attention,
    flash_attention,
    local_reduce,
    rwkv6,
    segment_reduce,
)

__all__ = [
    "decode_attention",
    "flash_attention",
    "local_reduce",
    "rwkv6",
    "segment_reduce",
]
