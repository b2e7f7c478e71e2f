"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package has:
- ``kernel.py`` : pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
- ``ops.py``    : jit'd public wrapper (dispatches kernel vs reference)
- ``ref.py``    : pure-jnp oracle, swept against the kernel in interpret mode

Hot spots (DESIGN.md §3): flash_attention (prefill/train attention),
hash_partition (shuffle phase 1), segment_reduce (groupby / MoE combine,
scatter re-expressed as an MXU one-hot matmul), join_probe (sorted-probe
phase of the distributed join).
"""

import jax
import jax.numpy as jnp

_SUBLANES = 8


def lane_dense(x: jax.Array, block: int, lanes: int) -> tuple[jax.Array, int]:
    """Zero-pad 1-D ``x`` into a ``[rows, lanes]`` layout for a grid over
    row blocks; returns it with the rows per block.  A block holds ``block``
    elements rounded up to whole (8, lanes) tiles, and no more rows than the
    padded array, so every block's last two dims are multiples of (8, 128)."""
    n = x.shape[0]
    block_rows = -(-max(block, 1) // (_SUBLANES * lanes)) * _SUBLANES
    rows = -(-n // lanes)
    block_rows = min(block_rows, -(-rows // _SUBLANES) * _SUBLANES)
    rows = -(-rows // block_rows) * block_rows
    return jnp.pad(x, (0, rows * lanes - n)).reshape(rows, lanes), block_rows
