"""Compile the cross-match cores for a TPU v5e that is described, not
attached.

The TPU compiler is installed with JAX, so these tests refuse here what
the chip's compiler would refuse (Mosaic layouts, VMEM limits) at no chip
time.  They compile only; nothing runs, so they say nothing of results or
speed.  Shapes are the cores' inputs after the host-side pow2 padding:

* the smallest: the pow2 floor of 8 rows on both operands;
* the paper's bucket width: 10,000 objects -> 16,384 bucket rows, against
  a 32,768-row probe batch (a 200-query trace over a 2M-object catalog
  sends up to 31,742 probes to one bucket).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.crossmatch import ops as cm_ops
from repro.kernels.crossmatch.kernel import COORD_PAD

SMALLEST = (8, 8)  # (bucket rows, probe rows)
PAPER = (16_384, 32_768)
COS_THR = 0.9999995  # cos(1e-3 rad), the engine's default radius


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, one_chip):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)


def _lower(core, shape, one_chip, use_pallas, band=None):
    n, m = shape
    bucket = _spec((n, COORD_PAD), one_chip)
    probes = _spec((m, COORD_PAD), one_chip)
    bm, bn = 128, 512  # the wrappers' defaults, which the engine uses
    if core == "single":
        return cm_ops._crossmatch_jit.lower(
            bucket, probes, COS_THR, use_pallas, bm, bn, band, False
        )
    bseg, pseg = _spec((n,), one_chip), _spec((m,), one_chip)
    if core == "fused":
        return cm_ops._crossmatch_fused_jit.lower(
            bucket, probes, bseg, pseg, COS_THR, use_pallas, bm, bn, False
        )
    thr = _spec((m,), one_chip)
    return cm_ops._crossmatch_shared_jit.lower(
        bucket, probes, bseg, pseg, thr, use_pallas, bm, bn, False
    )


@pytest.mark.parametrize("shape", [SMALLEST, PAPER], ids=["smallest", "paper"])
@pytest.mark.parametrize("core", ["single", "fused", "shared"])
def test_pallas_core_compiles(core, shape, one_chip, no_compile_cache):
    compiled = _lower(core, shape, one_chip, use_pallas=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_banded_kernel_compiles(one_chip, no_compile_cache):
    compiled = _lower("single", PAPER, one_chip, True, band=2).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("core", ["single", "fused", "shared"])
def test_jnp_core_compiles_at_highest_precision(core, one_chip, no_compile_cache):
    lowered = _lower(core, PAPER, one_chip, use_pallas=False)
    assert "HIGHEST" in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" not in compiled.as_text()
