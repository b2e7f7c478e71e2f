"""Public sorted-probe op: Pallas kernel on TPU, jnp oracle elsewhere."""

from __future__ import annotations

import jax

from repro.kernels.join_probe import kernel, ref

# the kernel holds the right page in SMEM; 32768 int32 = 128 KiB
_MAX_PAGE = 32768


def probe_sorted(right_keys, left_keys, *, force_kernel: bool = False):
    on_tpu = jax.default_backend() == "tpu"
    if not (force_kernel or on_tpu):
        return ref.probe_sorted_ref(right_keys, left_keys)
    if right_keys.shape[0] > _MAX_PAGE:
        raise ValueError(
            f"probe page of {right_keys.shape[0]} keys exceeds the kernel's "
            f"{_MAX_PAGE}-key SMEM page"
        )
    return kernel.probe_sorted(right_keys, left_keys, interpret=not on_tpu)
