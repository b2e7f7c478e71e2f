"""Blocked segment-sum Pallas kernel (groupby aggregate / MoE combine).

TPU adaptation: scatter-add is serial poison on the VPU, so the per-block
reduction is re-expressed as a ONE-HOT MATMUL on the MXU:

    partial[b, :] = onehot(local_seg[b]) @ values[b]      (msb x bn @ bn)

Rows are laid out as ``[n_blocks, block]`` (block a multiple of 128 lanes)
and each grid step takes 8 blocks, one (8, block) tile.  Segments are
assumed sorted (the groupby sorts first), so block ``b`` touches at most
``max_seg`` distinct segments starting at its first id ``bases[b]``;
``ops.py`` combines the ``[n_blocks, max_seg]`` partials with a cheap jnp
segment-sum over block offsets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SUBLANES = 8


def _segsum_kernel(seg_ref, val_ref, out_ref, *, max_seg: int):
    seg = seg_ref[...]                         # [8, bn] int32 (sorted rows)
    vals = val_ref[...]                        # [8, bn] f32
    local = seg - seg[:, 0:1]                  # in [0, msb) if within bound
    bn = seg.shape[1]
    seg_iota = jax.lax.broadcasted_iota(jnp.int32, (max_seg, bn), 0)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, max_seg), 0)
    acc = jnp.zeros((_SUBLANES, max_seg), jnp.float32)
    for r in range(_SUBLANES):
        onehot_t = (seg_iota == local[r:r + 1, :]).astype(jnp.float32)  # [msb, bn]
        # [8, msb] = [8, bn] @ [msb, bn]^T; only row r belongs to block r
        part = jax.lax.dot_general(
            vals, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        acc = jnp.where(row_iota == r, part, acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "max_seg", "interpret"))
def segment_sum_blocked(
    seg_ids: jax.Array,    # [n] int32, sorted ascending
    values: jax.Array,     # [n] float
    *,
    block: int = 1024,
    max_seg: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (partials [n_blocks, max_seg] f32, bases [n_blocks] int32).

    ``block`` must be a multiple of 128.  Rows whose segment exceeds
    base+max_seg within a block are NOT captured (one-hot row is all-zero);
    callers must choose max_seg >= max distinct segments per block (ops.py
    validates against the oracle in tests).
    """
    if block % 128:
        raise ValueError(f"block {block} is not a multiple of 128 lanes")
    n = seg_ids.shape[0]
    tile = _SUBLANES * block
    pad = (-n) % tile
    # pad with a sentinel segment that continues the last row's segment
    seg_p = jnp.pad(seg_ids, (0, pad), mode="edge")
    val_p = jnp.pad(values.astype(jnp.float32), (0, pad))
    rows = seg_p.shape[0] // block
    seg_b = seg_p.reshape(rows, block)
    val_b = val_p.reshape(rows, block)
    spec = pl.BlockSpec((_SUBLANES, block), lambda i: (i, 0))
    partials = pl.pallas_call(
        functools.partial(_segsum_kernel, max_seg=max_seg),
        grid=(rows // _SUBLANES,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((_SUBLANES, max_seg), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, max_seg), jnp.float32),
        interpret=interpret,
    )(seg_b, val_b)
    return partials, seg_b[:, 0]
