"""The main-path Pallas kernels compile for a TPU v5e, at their real sizes.

No chip is needed: the TPU compiler is installed, and it compiles for a
described ``v5e:2x2`` topology.  Interpret mode cannot catch what these
catch — a Mosaic lowering the compiler refuses, or a layout that aborts it
(which kills the process, so these kernels must compile before anything
here lands).  The topology is described in a fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lea
from repro.core.lagrange import CodeSpec
from repro.core.lea import LoadParams
from repro.kernels.gf.kernel import matmul_gf_pallas
from repro.kernels.poisson_binomial.kernel import (success_tails_pallas,
                                                   success_tails_pallas_w)

ENGINE_BATCH = 4096                      # rows of one engine allocate call
FIG3 = LoadParams(n=15, kstar=99, ell_g=10, ell_b=3)
EC2 = CodeSpec(15, 10, 120, deg_f=1)     # K* = 120
EC2_ROWS, EC2_COLS = 25, 3000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_poisson_binomial_static_w_compiles(one_chip):
    w = tuple(int(v) for v in lea.prefix_thresholds(FIG3))
    p = jax.ShapeDtypeStruct((ENGINE_BATCH, FIG3.n), jnp.float32,
                             sharding=one_chip)
    hlo = _compile(lambda x: success_tails_pallas(x, w), p)
    assert "tpu_custom_call" in hlo


def test_poisson_binomial_traced_w_compiles(one_chip):
    p = jax.ShapeDtypeStruct((ENGINE_BATCH, FIG3.n), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((ENGINE_BATCH, FIG3.n), jnp.int32,
                             sharding=one_chip)
    hlo = _compile(success_tails_pallas_w, p, w)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("stage", ["encode", "worker_products", "decode"])
def test_gf_matmul_compiles_at_ec2_shapes(one_chip, stage):
    nr, k, kstar = EC2.nr, EC2.k, EC2.recovery_threshold
    m, c, n = {
        "encode": (nr, k, EC2_ROWS * EC2_COLS),      # generator @ data
        "worker_products": (nr * EC2_ROWS, EC2_COLS, 1),  # shards @ w
        "decode": (k, kstar, EC2_ROWS),              # decode @ results
    }[stage]
    a = jax.ShapeDtypeStruct((m, c), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((c, n), jnp.uint32, sharding=one_chip)
    hlo = _compile(matmul_gf_pallas, a, b)
    assert "tpu_custom_call" in hlo
