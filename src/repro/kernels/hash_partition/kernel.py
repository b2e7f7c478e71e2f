"""Row-hash + bucket-id Pallas kernel (shuffle phase 1).

Elementwise murmur-style finalizer over integer keys; one VMEM block of keys
per grid step, fused hash -> bucket modulo so the partition phase reads keys
from HBM exactly once.  Keys are laid out lane-dense as ``[rows, 1024]`` and
each grid step takes ``block // 1024`` rows (8 x 1024 int32 = 32 KiB at the
default), so every block is a whole number of (8, 128) tiles.  The op is
memory-bound; the kernel's job is simply to not waste the single pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import lane_dense

_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_SEED_MIX = 0x9E3779B9
_LANES = 1024


def _hash_kernel(x_ref, h_ref, b_ref, *, seed: int, num_partitions: int):
    seed_mixed = (seed * _SEED_MIX + 1) & 0xFFFFFFFF
    h = x_ref[...].astype(jnp.uint32) ^ jnp.uint32(seed_mixed)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    h = h ^ (h >> 16)
    h_ref[...] = h
    b_ref[...] = (h % jnp.uint32(num_partitions)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_partitions", "seed", "block", "interpret"))
def hash_partition(
    keys: jax.Array,       # [n] int32/uint32
    *,
    num_partitions: int,
    seed: int = 0,
    block: int = 8192,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``block`` keys per grid step, rounded up to whole (8, 1024) tiles."""
    x, block_rows = lane_dense(keys, block, _LANES)
    rows = x.shape[0]
    spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    kernel = functools.partial(_hash_kernel, seed=seed, num_partitions=num_partitions)
    h, b = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), jnp.uint32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    n = keys.shape[0]
    return h.reshape(-1)[:n], b.reshape(-1)[:n]
