"""Sharding rules + small-mesh dry-run (subprocess with 8 host devices)."""

import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.models import transformer as tf
from repro.sharding import rules


class TestParamSpecs:
    def _specs(self, arch="llama3-8b", **kw):
        cfg = smoke_config(arch)
        params = jax.eval_shape(
            lambda k: tf.init_params(cfg, k), jax.random.PRNGKey(0)
        )
        return params, rules.param_specs(params, rules.MeshAxes(), **kw)

    def test_every_leaf_has_matching_rank(self):
        for arch in ("llama3-8b", "jamba-v0.1-52b", "rwkv6-3b",
                     "arctic-480b", "hubert-xlarge"):
            params, specs = self._specs(arch)
            jax.tree.map(
                lambda p, s: None if len(s) == len(p.shape) else
                pytest.fail(f"rank mismatch {s} vs {p.shape}"),
                params, specs,
                is_leaf=lambda x: isinstance(x, P),
            )

    def test_tp_rules_applied(self):
        _, specs = self._specs()
        blk = specs["blocks"]["pos0"]
        assert blk["attn"]["wq"] == P(None, None, "model")
        assert blk["attn"]["wo"] == P(None, "model", None)
        assert blk["ffn"]["w_down"] == P(None, "model", None)
        assert specs["embed"] == P("model", None)

    def test_fsdp_adds_data_axis(self):
        _, plain = self._specs(fsdp=False)
        _, fsdp = self._specs(fsdp=True, fsdp_min_size=8)
        wq_plain = plain["blocks"]["pos0"]["attn"]["wq"]
        wq_fsdp = fsdp["blocks"]["pos0"]["attn"]["wq"]
        assert "data" not in jax.tree.leaves(tuple(wq_plain or ()))
        assert "data" in (wq_fsdp or ())

    def test_divisibility_sanitization(self):
        params, _ = self._specs("hubert-xlarge")
        specs = rules.param_specs(
            params, rules.MeshAxes(),
            mesh_shape={"data": 4, "model": 3},  # 3 divides nothing here
        )
        head = specs["lm_head"]
        assert head == P(None, None)  # vocab_padded 512 % 3 != 0 -> dropped

    def test_moe_expert_parallel(self):
        _, specs = self._specs("arctic-480b")
        moe = specs["blocks"]["pos0"]["moe"]
        assert moe["w_gate"][1] == "model"  # (stack, E, D, F): E on model


class TestDecodeStateSpecs:
    def test_kv_fallback_hierarchy(self):
        cfg = smoke_config("llama3-8b")
        # kv heads = 2, model axis 4 -> heads not divisible -> seq gets model
        state = jax.eval_shape(
            lambda: tf.init_decode_state(cfg, 8, 64)
        )
        specs = rules.decode_state_specs(
            state["layers"], rules.MeshAxes(),
            mesh_shape={"data": 4, "model": 4},
        )
        kv = specs["pos0"]["kv"]["k"]
        assert kv == P(None, "data", "model", None, None)

    def test_batch1_sequence_parallel(self):
        cfg = smoke_config("jamba-v0.1-52b")
        state = jax.eval_shape(
            lambda: tf.init_decode_state(cfg, 1, 256)
        )
        specs = rules.decode_state_specs(
            state["layers"], rules.MeshAxes(),
            mesh_shape={"data": 4, "model": 4},
        )
        kv = specs["pos4"]["kv"]["k"]
        assert kv == P(None, None, ("data", "model"), None, None)


_DRYRUN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import repro.configs as C
from repro.launch.mesh import make_mesh
from repro.launch import cells

small = {
    "train_4k": dataclasses.replace(C.SHAPES["train_4k"], seq_len=128,
                                    global_batch=8),
    "prefill_32k": dataclasses.replace(C.SHAPES["prefill_32k"], seq_len=256,
                                       global_batch=4),
    "decode_32k": dataclasses.replace(C.SHAPES["decode_32k"], seq_len=256,
                                      global_batch=8),
    "long_500k": dataclasses.replace(C.SHAPES["long_500k"], seq_len=1024,
                                     global_batch=1),
}
C.SHAPES.clear(); C.SHAPES.update(small)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))  # multi-pod shape
for arch in ("llama3-8b", "granite-moe-1b-a400m", "rwkv6-3b"):
    cfg = dataclasses.replace(C.smoke_config(arch), param_dtype="bfloat16")
    for shape in C.applicable_shapes(cfg):
        r = cells.analyze_cell_extrapolated(arch, shape, mesh, cfg=cfg)
        roof = r["roofline"]
        assert roof["compute_s"] > 0, (arch, shape)
        assert roof["dominant"] in ("compute", "memory", "collective")
        assert r["memory"]["peak_bytes"] > 0
print("DRYRUN_SMALL_OK")
"""


@pytest.mark.slow
def test_small_multipod_dryrun(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _DRYRUN_SCRIPT],
        capture_output=True, text=True, timeout=580,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert "DRYRUN_SMALL_OK" in proc.stdout, proc.stderr[-3000:]
