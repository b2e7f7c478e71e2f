"""Fixed-capacity columnar Table — the in-memory unit of the DDMF.

Design constraints (and how they differ from Arrow, per DESIGN.md §2):

- XLA needs static shapes, so a Table owns `capacity` rows of storage and a
  dynamic `count` of valid rows; rows at index >= count are padding.
- All columns share the row axis; a column may have trailing feature dims.
- A Table is a JAX pytree, so it passes through jit/shard_map/scan freely.

Invalid (padding) rows are *never* trusted to hold any particular value;
every operator masks by `count`.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

# The device work of every operator, by stage.  Each stage's work runs inside
# ``jax.named_scope(<stage>)``, so every HLO instruction it lowers to carries
# the name in its ``op_name`` metadata; ``chipbench/stages.py`` reads each
# stage's device time from a profiler trace by it.  A scope is compile-time
# metadata: the compiled instructions are the same with or without it.  No
# name equals a JAX primitive's, so a path component is never mistaken for one.
STAGES = (
    "partition_hash",  # partition.bucket_ids: row hash, bucket, padding sentinel
    "partition_pack",  # build_partition_payload: argsort by bucket, scatter to [P, cap]
    "exchange",        # shuffle_spmd's all_to_alls (and the wire codec)
    "receive_pack",    # from_stacked: pack the P x cap received slots
    "key_sort",        # ops_local.sort_by_key: argsort and permutation gather
    "probe",           # join_unique: co-sort of both key sets, scans for lower bound and hit
    "column_gather",   # join_unique: the right table's columns at the probe positions
    "output_pack",     # join_unique: stable pack of the matched rows (Table.filter)
    "segment_reduce",  # groupby_agg: segment ids, segmented scans, compacting sort, padding zeroed
)


def stage(name: str):
    """The named scope of one of :data:`STAGES`."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; have {STAGES}")
    return jax.named_scope(name)


@dataclasses.dataclass(frozen=True)
class Schema:
    names: tuple[str, ...]
    dtypes: tuple[jnp.dtype, ...]
    trailing: tuple[tuple[int, ...], ...]  # per-column feature dims (beyond rows)

    def __str__(self) -> str:
        cols = ", ".join(
            f"{n}:{jnp.dtype(d).name}{list(t) if t else ''}"
            for n, d, t in zip(self.names, self.dtypes, self.trailing)
        )
        return f"Schema({cols})"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Table:
    """Columnar table: dict of [capacity, ...] arrays + valid-row count."""

    columns: dict[str, jax.Array]
    count: jax.Array  # int32 scalar

    # -- pytree protocol ------------------------------------------------------

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[n] for n in names) + (self.count,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        *cols, count = children
        return cls(dict(zip(names, cols)), count)

    # -- construction ---------------------------------------------------------

    @classmethod
    def from_dict(
        cls, data: Mapping[str, np.ndarray], capacity: int | None = None
    ) -> Table:
        arrays = {k: np.asarray(v) for k, v in data.items()}
        lengths = {v.shape[0] for v in arrays.values()}
        if len(lengths) != 1:
            raise ValueError(f"ragged columns: {lengths}")
        n = lengths.pop()
        cap = capacity or n
        if cap < n:
            raise ValueError(f"capacity {cap} < rows {n}")
        cols = {}
        for k, v in arrays.items():
            pad = np.zeros((cap - n,) + v.shape[1:], dtype=v.dtype)
            cols[k] = jnp.asarray(np.concatenate([v, pad], axis=0))
        return cls(cols, jnp.asarray(n, jnp.int32))

    @classmethod
    def empty_like(cls, other: Table, capacity: int | None = None) -> Table:
        cap = capacity or other.capacity
        cols = {
            k: jnp.zeros((cap,) + v.shape[1:], v.dtype)
            for k, v in other.columns.items()
        }
        return cls(cols, jnp.asarray(0, jnp.int32))

    # -- introspection --------------------------------------------------------

    @property
    def capacity(self) -> int:
        return next(iter(self.columns.values())).shape[0]

    @property
    def schema(self) -> Schema:
        names = tuple(sorted(self.columns))
        return Schema(
            names,
            tuple(self.columns[n].dtype for n in names),
            tuple(tuple(self.columns[n].shape[1:]) for n in names),
        )

    def valid_mask(self) -> jax.Array:
        return jnp.arange(self.capacity) < self.count

    def nbytes(self) -> int:
        return sum(int(np.prod(v.shape)) * v.dtype.itemsize for v in self.columns.values())

    # -- materialization (host side; trims padding) ---------------------------

    def to_numpy(self) -> dict[str, np.ndarray]:
        n = int(self.count)
        return {k: np.asarray(v)[:n] for k, v in self.columns.items()}

    def __repr__(self) -> str:
        try:
            n = int(self.count)
        except Exception:  # traced
            n = -1
        return f"Table(rows={n}, capacity={self.capacity}, {self.schema})"

    # -- relational basics (all jit-safe) --------------------------------------

    def project(self, names: Iterable[str]) -> Table:
        return Table({n: self.columns[n] for n in names}, self.count)

    def with_column(self, name: str, values: jax.Array) -> Table:
        if values.shape[0] != self.capacity:
            raise ValueError("column capacity mismatch")
        cols = dict(self.columns)
        cols[name] = values
        return Table(cols, self.count)

    def gather(self, idx: jax.Array, new_count: jax.Array) -> Table:
        """Reorder/select rows by index (out-of-range drops are caller's job)."""
        cols = {k: jnp.take(v, idx, axis=0, mode="clip") for k, v in self.columns.items()}
        return Table(cols, jnp.asarray(new_count, jnp.int32))

    def filter(self, pred: jax.Array) -> Table:
        """Keep rows where `pred` (and valid); result is packed to the front."""
        keep = pred & self.valid_mask()
        # stable pack: order by (not keep), preserving row order inside groups
        order = jnp.argsort(jnp.where(keep, 0, 1), stable=True)
        return self.gather(order, jnp.sum(keep.astype(jnp.int32)))

    def head(self, n: int) -> Table:
        cols = {k: v[:n] for k, v in self.columns.items()}
        return Table(cols, jnp.minimum(self.count, n).astype(jnp.int32))


def concat(tables: list[Table]) -> Table:
    """Concatenate padded tables, repacking valid rows to the front."""
    if not tables:
        raise ValueError("concat of no tables")
    names = sorted(tables[0].columns)
    for t in tables[1:]:
        if sorted(t.columns) != names:
            raise ValueError("schema mismatch in concat")
    cols = {
        n: jnp.concatenate([t.columns[n] for t in tables], axis=0) for n in names
    }
    mask = jnp.concatenate([t.valid_mask() for t in tables], axis=0)
    order = jnp.argsort(jnp.where(mask, 0, 1), stable=True)
    count = sum(t.count for t in tables)
    out = Table(cols, jnp.asarray(count, jnp.int32))
    return out.gather(order, count)


def from_stacked(columns: dict[str, jax.Array], counts: jax.Array) -> Table:
    """Build a Table from [P, cap, ...] stacked buckets + per-bucket counts,
    packing all valid rows to the front (the receive side of a shuffle)."""
    p, cap = counts.shape[0], next(iter(columns.values())).shape[1]
    with stage("receive_pack"):
        flat = {k: v.reshape((p * cap,) + v.shape[2:]) for k, v in columns.items()}
        within = jnp.tile(jnp.arange(cap), p)
        bucket = jnp.repeat(jnp.arange(p), cap)
        mask = within < counts[bucket]
        order = jnp.argsort(jnp.where(mask, 0, 1), stable=True)
        total = jnp.sum(counts).astype(jnp.int32)
        return Table(flat, total).gather(order, total)
