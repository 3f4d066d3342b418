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
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lea
from repro.core.lagrange import CodeSpec
from repro.core.lea import LoadParams
from repro.kernels.gf import bmm_gf
from repro.kernels.gf.kernel import matmul_gf_pallas
from repro.kernels.poisson_binomial import WIDE_N
from repro.kernels.poisson_binomial.kernel import (success_tails_pallas,
                                                   success_tails_pallas_w,
                                                   success_tails_wide)

ENGINE_BATCH = 4096                      # rows of one engine allocate call
# a chip's share of the four-chip fleet sweep: 32 sweep rows, each one DP row
# a round of a 250-round block, vmapped as the engine calls the DP
FLEET_ROWS, FLEET_CHUNK = 32, 250
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


def _compile_wide(one_chip, n):
    p = jax.ShapeDtypeStruct((FLEET_ROWS, FLEET_CHUNK, n), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((FLEET_ROWS, FLEET_CHUNK, n), jnp.int32,
                             sharding=one_chip)
    t0 = time.perf_counter()
    hlo = _compile(jax.vmap(success_tails_wide), p, w)
    return hlo, time.perf_counter() - t0


def test_poisson_binomial_wide_compiles_at_fleet_width(one_chip):
    """The wide body at the cut and at 4,000 workers: the worker loop is
    rolled, so the compile does not grow with n (the unrolled body took 40 s
    and ran out of VMEM at n = 128)."""
    at_cut, t_cut = _compile_wide(one_chip, WIDE_N)
    at_fleet, t_fleet = _compile_wide(one_chip, 4000)
    for hlo in (at_cut, at_fleet):
        assert "tpu_custom_call" in hlo
        assert "repro.dp_wide" in hlo
    assert t_fleet < 3 * t_cut + 5.0, (t_cut, t_fleet)


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
    # the products and the decode are narrow (n < 128): the lane contraction
    assert ("repro.gf_matvec" in hlo) == (stage != "encode")


def test_gf_bmm_compiles_at_ec2_gradient_shape(one_chip):
    """The exact gradient's vmapped per-chunk products, X~_v^T (X~_v w - y~_v):
    (cols, rows) @ (rows, 1) for every one of the n r chunks, narrow body."""
    a = jax.ShapeDtypeStruct((EC2.nr, EC2_COLS, EC2_ROWS), jnp.uint32,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((EC2.nr, EC2_ROWS, 1), jnp.uint32,
                             sharding=one_chip)
    hlo = _compile(lambda x, y: bmm_gf(x, y, impl="pallas", interpret=False),
                   a, b)
    assert "tpu_custom_call" in hlo
    assert "repro.gf_matvec" in hlo
