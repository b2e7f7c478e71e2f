"""Hash partitioning — step 1 of every shuffle-based operator (paper §III-D:
"1) Hash applicable columns into partitioned tables, 2) Use AllToAll ...,
3) Execute a local join").

The row-hash is the compute hot-spot of the partition phase; `hash32` is the
jnp reference implementation and the Pallas kernel in
``repro.kernels.hash_partition`` is the TPU-tiled version (ops.py dispatches).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dataframe.table import Table, stage

_M1 = jnp.uint32(0x85EBCA6B)
_M2 = jnp.uint32(0xC2B2AE35)
_SEED_MIX = jnp.uint32(0x9E3779B9)


def hash32(x: jax.Array, seed: int = 0) -> jax.Array:
    """Murmur3-style 32-bit finalizer over integer keys (vectorized).

    Deterministic across platforms/world sizes — a partition-totality
    invariant the property tests pin down.
    """
    h = x.astype(jnp.uint32) ^ (jnp.uint32(seed) * _SEED_MIX + jnp.uint32(1))
    h = h ^ (h >> 16)
    h = h * _M1
    h = h ^ (h >> 13)
    h = h * _M2
    h = h ^ (h >> 16)
    return h


def hash_columns(table: Table, key_cols: list[str], seed: int = 0) -> jax.Array:
    """Combine per-column hashes into one row hash (boost-style mixing)."""
    h = jnp.full((table.capacity,), jnp.uint32(seed) ^ jnp.uint32(0x51ED270B), jnp.uint32)
    for c in key_cols:
        col = table.columns[c]
        if col.ndim != 1:
            raise ValueError(f"key column {c} must be 1-D")
        ch = hash32(col, seed)
        h = h ^ (ch + _SEED_MIX + (h << 6) + (h >> 2))
    return h


def bucket_ids(table: Table, key_cols: list[str], num_partitions: int, seed: int = 0) -> jax.Array:
    """Destination partition per row; padding rows get the sentinel P."""
    with stage("partition_hash"):
        h = hash_columns(table, key_cols, seed)
        b = (h % jnp.uint32(num_partitions)).astype(jnp.int32)
        return jnp.where(table.valid_mask(), b, num_partitions)


def partition_counts(table: Table, key_cols: list[str], num_partitions: int, seed: int = 0) -> jax.Array:
    b = bucket_ids(table, key_cols, num_partitions, seed)
    return jnp.bincount(b, length=num_partitions + 1)[:num_partitions].astype(jnp.int32)


def build_partition_payload(
    table: Table,
    num_partitions: int,
    key_cols: list[str],
    cap_per_dest: int | None = None,
    seed: int = 0,
) -> tuple[dict[str, jax.Array], jax.Array]:
    """Bucket rows by hash(key) % P into a fixed-capacity send buffer.

    Returns (payload, counts): payload[col] is ``[P, cap_per_dest, ...]`` with
    partition d's rows packed at the front of slot d; counts is ``[P]`` int32.
    Rows beyond `cap_per_dest` in a slot are dropped *and reflected in counts
    clamping* — callers size capacity via `partition_counts` or accept the
    skew bound (tests cover both).
    """
    p = num_partitions
    cap_dst = cap_per_dest or table.capacity
    b = bucket_ids(table, key_cols, p, seed)

    with stage("partition_pack"):
        # Stable sort rows by bucket so each partition's rows are contiguous.
        order = jnp.argsort(b, stable=True)
        b_sorted = b[order]
        counts_full = jnp.bincount(b, length=p + 1)[: p]
        counts = jnp.minimum(counts_full, cap_dst).astype(jnp.int32)
        starts = jnp.cumsum(counts_full) - counts_full  # [P] group starts in sorted order

        pos_in_group = jnp.arange(table.capacity) - jnp.take(
            starts, jnp.minimum(b_sorted, p - 1), mode="clip"
        )
        dest_row = jnp.where(
            (b_sorted < p) & (pos_in_group < cap_dst), pos_in_group, cap_dst
        )  # cap_dst == drop slot
        dest_slot = jnp.minimum(b_sorted, p - 1)

        payload = {}
        for name, col in table.columns.items():
            src = col[order]
            buf = jnp.zeros((p, cap_dst + 1) + col.shape[1:], col.dtype)
            buf = buf.at[dest_slot, dest_row].set(src, mode="drop")
            payload[name] = buf[:, :cap_dst]
        return payload, counts
