"""Public hash-partition op: Pallas kernel on TPU, jnp oracle elsewhere."""

from __future__ import annotations

import jax

from repro.kernels.hash_partition import kernel, ref


def hash_partition(keys, *, num_partitions: int, seed: int = 0, force_kernel: bool = False):
    on_tpu = jax.default_backend() == "tpu"
    if force_kernel or on_tpu:
        return kernel.hash_partition(
            keys, num_partitions=num_partitions, seed=seed, interpret=not on_tpu,
        )
    return ref.hash_partition_ref(keys, num_partitions=num_partitions, seed=seed)
