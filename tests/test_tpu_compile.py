"""The Pallas kernels compile for a TPU v5e chip, described and not attached.

Interpret mode accepts blocks and primitives the TPU compiler refuses, so
these tests hand both MapReduce kernels to the real compiler (installed
with jax) at the widest partition the pallas backend accepts (``MAX_C``)
and at a mid width, and check the compiled program holds the kernel as a
``tpu_custom_call`` rather than an interpreted loop.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.local_reduce.kernel import local_reduce_fwd
from repro.kernels.segment_reduce import MAX_C
from repro.kernels.segment_reduce.kernel import segment_reduce_fwd
from repro.mapreduce import JobConfig, backends, build_job, wordcount

KERNELS = {"segment_reduce": segment_reduce_fwd, "local_reduce": local_reduce_fwd}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("C", [1000, MAX_C])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, C):
    fwd = KERNELS[kernel]
    keys = jax.ShapeDtypeStruct((5, C), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((5, C), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda k, v: fwd(k, v, interpret=False)
    ).lower(keys, vals).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("combiner", [False, True])
def test_pallas_engine_job_compiles_for_v5e(one_chip, monkeypatch, combiner):
    """The engine's pallas-backend WordCount job, as ``chip_smoke.py`` runs
    it (M = R = 8, partitions exactly ``MAX_C`` wide), compiles with the
    kernels as TPU custom calls.  The test process runs on the CPU
    platform, so the interpret switch is pinned to the TPU's answer."""
    monkeypatch.setattr(backends, "pallas_interpret", lambda: False)
    n = 4096
    cfg = JobConfig(num_mappers=8, num_reducers=8, capacity_factor=4.0,
                    reduce_backend="pallas", combiner=combiner)
    tokens = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = build_job(wordcount(4096), cfg, n).lower(tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()
