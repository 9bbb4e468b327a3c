"""The main path compiles for a TPU v5e, at com-youtube's real shapes.

Nothing here runs on a chip: each test compiles for one device of a
described ``v5e:2x2`` topology, which raises whatever the TPU compiler
(Mosaic included) would refuse on the chip — block shapes off the tiling,
scalar memory or device memory overflow. Shapes are com-youtube's at
``slice_bits=64`` (|V| = 1,134,890, |E| = 2,987,624: 2^22-edge and
2^22-row store buckets, 44.5M row-slice candidates) and the executor's and
server's pair chunks. The topology is described only inside a fixture, so
importing this file loads no TPU library, and every xdist worker collects
the same tests.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import build as build_mod
from repro.core.executor import _chunk_step_fn, _fused_step_fn
from repro.kernels.tc_gather_popcount import (
    gather_segment_totals_pallas,
    gather_total_pallas,
)

YOUTUBE_N = 1_134_890
EDGE_BUCKET = 1 << 22  # pow2 bucket of |E| = 2,987,624
STORE_ROWS = 1 << 22  # pow2 bucket of ~2.6M valid slices per side
CAND_BUCKET = 1 << 26  # pow2 bucket of 44,510,150 candidates
CHUNK = 1 << 20  # Executor / ServeConfig default chunk_pairs
W = 2  # words per slice at slice_bits=64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory on one described v5e chip. The persistent
    compilation cache is off meanwhile: an entry compiled for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_gather_total_kernel_compiles(shape):
    store = shape((STORE_ROWS, W), jnp.uint32)
    idx = shape((CHUNK,), jnp.int32)
    compiled = _compile(
        functools.partial(gather_total_pallas, interpret=False),
        store, store, idx, idx,
    )
    assert _has_kernel(compiled)


@pytest.mark.parametrize(
    "graphs,bucket",
    [(32, 1 << 14), (32, 1 << 20)],  # ServeConfig default; the smoke's wave
)
def test_gather_segment_kernel_compiles(shape, graphs, bucket):
    store = shape((1 << 20, W), jnp.uint32)
    idx = shape((graphs * bucket,), jnp.int32)
    compiled = _compile(
        functools.partial(
            gather_segment_totals_pallas, bucket=bucket, interpret=False
        ),
        store, store, idx, idx,
    )
    assert _has_kernel(compiled)


def test_executor_chunk_step_compiles(shape):
    """The executor's device-resident chunk step, on the kernel path."""
    step = _chunk_step_fn("fused", False, True, "acc")
    store = shape((STORE_ROWS, W), jnp.uint32)
    idx = shape((CHUNK,), jnp.int32)
    compiled = step.lower(store, store, idx, idx, shape((), jnp.int32)).compile()
    assert _has_kernel(compiled)


def test_fused_serving_step_compiles(shape):
    step = _fused_step_fn(1 << 14, False, True)
    store = shape((1 << 20, W), jnp.uint32)
    idx = shape((32 << 14,), jnp.int32)
    assert _has_kernel(step.lower(store, store, idx, idx).compile())


def test_device_build_sbf_step_compiles(shape):
    sbf_step = build_mod._get_jits()["sbf"]
    edges = shape((EDGE_BUCKET,), jnp.int32)
    compiled = sbf_step.lower(
        edges, edges, shape((), jnp.int32), YOUTUBE_N, 64
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


def test_device_build_worklist_step_compiles(shape):
    worklist_step = build_mod._get_jits()["worklist"]
    edges = shape((EDGE_BUCKET,), jnp.int32)
    ptr = shape((YOUTUBE_N + 1,), jnp.int32)
    idx = shape((STORE_ROWS,), jnp.int32)
    compiled = worklist_step.lower(
        edges, edges, shape((), jnp.int32), ptr, idx, ptr, idx, CAND_BUCKET
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30
