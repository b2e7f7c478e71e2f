"""Sorted-probe join Pallas kernel.

For each left key, the lower-bound position in a sorted right-key page and
whether the key is present.  The right page (<= ``ops._MAX_PAGE`` keys) sits
in SMEM for the whole grid (HBM reads the probe side exactly once); the grid
walks lane-dense ``[block // 128, 128]`` tiles of left keys.  Each step
streams the page as scalars and counts, per lane, the right keys below the
left key (the lower bound) and those equal to it (the hit): pure VPU
compares and adds, no gathers and no data-dependent control flow.  Mosaic
lowers no vector gather across a page, so a per-lane binary search is not
expressible; the scan costs O(page) per key instead of O(log page).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lane_dense

_LANES = 128
_UNROLL = 8


def _probe_kernel(rk_ref, lk_ref, idx_ref, hit_ref, *, page: int):
    lkeys = lk_ref[...]                    # [block_rows, 128] int32

    def count(j, carry):
        below, equal = carry
        r = rk_ref[j]
        return below + (lkeys > r).astype(jnp.int32), equal + (lkeys == r).astype(jnp.int32)

    def unrolled(g, carry):
        for u in range(_UNROLL):
            carry = count(g * _UNROLL + u, carry)
        return carry

    zeros = jnp.zeros_like(lkeys)
    carry = jax.lax.fori_loop(0, page // _UNROLL, unrolled, (zeros, zeros))
    for j in range(page - page % _UNROLL, page):
        carry = count(j, carry)
    below, equal = carry
    idx_ref[...] = jnp.minimum(below, page - 1)
    hit_ref[...] = (equal > 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def probe_sorted(
    right_keys: jax.Array,   # [page] int32 sorted, padded with INT32_MAX
    left_keys: jax.Array,    # [n] int32
    *,
    block: int = 4096,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``block`` left keys per grid step, rounded up to whole (8, 128) tiles."""
    page = right_keys.shape[0]
    lk, block_rows = lane_dense(left_keys, block, _LANES)
    rows = lk.shape[0]
    spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    idx, hit = pl.pallas_call(
        functools.partial(_probe_kernel, page=page),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(right_keys, lk)
    n = left_keys.shape[0]
    return idx.reshape(-1)[:n], hit.reshape(-1)[:n].astype(jnp.bool_)
