"""Shared in-kernel helpers for the TCIM Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["swar_popcount_u32", "on_cpu", "on_tpu"]


def swar_popcount_u32(x: jax.Array) -> jax.Array:
    """Per-element popcount of a uint32 array via SWAR bit-twiddling.

    This is the VPU-friendly analogue of the paper's sense-amp 8->256 LUT
    BitCount: pure shift/mask/add lane arithmetic, no table, no gather.
    Returns int32 counts in [0, 32].
    """
    x = x.astype(jnp.uint32)
    c1 = jnp.uint32(0x55555555)
    c2 = jnp.uint32(0x33333333)
    c4 = jnp.uint32(0x0F0F0F0F)
    x = x - ((x >> jnp.uint32(1)) & c1)
    x = (x & c2) + ((x >> jnp.uint32(2)) & c2)
    x = (x + (x >> jnp.uint32(4))) & c4
    # Horizontal byte-sum via shift-adds (avoids a u32 multiply, which some
    # backends lower poorly).
    x = x + (x >> jnp.uint32(8))
    x = x + (x >> jnp.uint32(16))
    return (x & jnp.uint32(0x3F)).astype(jnp.int32)


def on_cpu() -> bool:
    """True when running on the CPU backend (Pallas requires interpret mode)."""
    return jax.default_backend() == "cpu"


def on_tpu() -> bool:
    """True on real TPUs — where the gather primitives run the Pallas kernel
    (``ops.gather_kernel_selected``)."""
    return jax.default_backend() == "tpu"
