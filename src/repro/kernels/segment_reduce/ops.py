"""Public segment-sum op: blocked kernel partials + jnp combine."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce import kernel, ref


def segment_sum(
    seg_ids: jax.Array,
    values: jax.Array,
    num_segments: int,
    *,
    block: int = 1024,
    max_seg: int = 128,
    force_kernel: bool = False,
) -> jax.Array:
    """Sorted-segment sum; kernel path on TPU (or forced), oracle otherwise."""
    on_tpu = jax.default_backend() == "tpu"
    if not (force_kernel or on_tpu):
        return ref.segment_sum_ref(seg_ids, values, num_segments)
    partials, bases = kernel.segment_sum_blocked(
        seg_ids, values, block=block, max_seg=max_seg, interpret=not on_tpu,
    )
    # combine: partial j of block i belongs to segment bases[i] + j
    seg_flat = (bases[:, None] + jnp.arange(max_seg)[None, :]).reshape(-1)
    seg_flat = jnp.clip(seg_flat, 0, num_segments)  # overflow slot dropped below
    out = jax.ops.segment_sum(
        partials.reshape(-1), seg_flat, num_segments=num_segments + 1
    )
    return out[:num_segments]
