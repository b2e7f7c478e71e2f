"""Local (single-partition) relational operators.

These are the "local join on the received tables" / local aggregation halves
of the paper's distributed operators.  All are jit-safe over fixed-capacity
tables; variable-size results use capacity + count + packing.

Algorithms are TPU-minded: sort-based (argsort lowers to a bitonic network on
TPU), branchless binary-search probes (the Pallas kernel in
``repro.kernels.join_probe`` implements the probe loop with VMEM tiling), and
segment reductions (``repro.kernels.segment_reduce``).
"""

from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.dataframe.table import Table, stage


def _key_sentinel(dtype) -> int:
    return jnp.iinfo(dtype).max


def sort_by_key(table: Table, key: str) -> Table:
    """Sort valid rows ascending by integer key; padding stays at the back."""
    with stage("key_sort"):
        keys = table.columns[key]
        sent = _key_sentinel(keys.dtype)
        masked = jnp.where(table.valid_mask(), keys, sent)
        order = jnp.argsort(masked, stable=True)
        return table.gather(order, table.count)


# ---------------------------------------------------------------------------
# GroupBy (paper §IV-C) — sort + segment reduce, with combiner support
# ---------------------------------------------------------------------------

AGGS: dict[str, Callable] = {
    "sum": lambda vals, seg, n: jax.ops.segment_sum(vals, seg, num_segments=n),
    "max": lambda vals, seg, n: jax.ops.segment_max(vals, seg, num_segments=n),
    "min": lambda vals, seg, n: jax.ops.segment_min(vals, seg, num_segments=n),
    "count": lambda vals, seg, n: jax.ops.segment_sum(jnp.ones_like(vals), seg, num_segments=n),
}


def groupby_agg(table: Table, key: str, aggs: dict[str, str]) -> Table:
    """Group by integer `key`; aggregate value columns with AGGS ops.

    Output: one row per distinct key (packed), capacity preserved.
    `aggs` maps value-column name -> op name.  The mean op is expressed by the
    caller as sum+count (associativity needed for the distributed combiner).
    """
    t = sort_by_key(table, key)
    with stage("segment_reduce"):
        keys = t.columns[key]
        valid = t.valid_mask()
        sent = _key_sentinel(keys.dtype)
        keys_m = jnp.where(valid, keys, sent)
        cap = table.capacity

        # Segment ids: 0-based rank of each distinct key in sorted order; invalid
        # rows are parked in an overflow segment `cap` that is sliced away.
        new_seg = jnp.concatenate(
            [jnp.ones((1,), jnp.int32), (keys_m[1:] != keys_m[:-1]).astype(jnp.int32)]
        )
        new_seg = jnp.where(valid, new_seg, 0)
        seg = jnp.where(valid, jnp.cumsum(new_seg) - 1, cap)
        n_groups = jnp.sum(new_seg).astype(jnp.int32)

        out_cols: dict[str, jax.Array] = {}
        # representative key per group (all rows in a segment share the key)
        kmin = jnp.iinfo(keys.dtype).min
        out_cols[key] = jax.ops.segment_max(
            jnp.where(valid, keys, kmin), seg, cap + 1
        )[:cap].astype(keys.dtype)
        for col, op in aggs.items():
            vals = t.columns[col]
            if op not in AGGS:
                raise ValueError(f"unsupported agg {op!r}; have {sorted(AGGS)}")
            res = AGGS[op](vals, seg, cap + 1)[:cap]
            out_cols[f"{col}_{op}"] = res.astype(
                jnp.int32 if op == "count" else table.columns[col].dtype
            )

        out = Table(out_cols, n_groups)
        # zero padding rows for determinism (segment_max yields dtype-min there)
        mask = out.valid_mask()
        cols = {
            k: jnp.where(mask.reshape((-1,) + (1,) * (v.ndim - 1)), v, 0)
            for k, v in out.columns.items()
        }
        return Table(cols, out.count)


# ---------------------------------------------------------------------------
# Joins
# ---------------------------------------------------------------------------


def join_unique(left: Table, right: Table, key: str, how: str = "inner") -> Table:
    """Equi-join where `right` has at most one valid row per key.

    Sort-probe: sort right by key, binary-search each left key.  This is the
    paper's microbenchmark regime (uniform random ~unique keys) and the
    kernelized path (repro.kernels.join_probe).  Inner join only here;
    unmatched left rows are dropped (packed out).
    """
    if how != "inner":
        raise NotImplementedError("join_unique supports inner joins")
    r = sort_by_key(right, key)
    with stage("probe"):
        rkeys = jnp.where(r.valid_mask(), r.columns[key], _key_sentinel(r.columns[key].dtype))
        lkeys = left.columns[key]
        pos = jnp.searchsorted(rkeys, lkeys)
        pos_c = jnp.clip(pos, 0, right.capacity - 1)
        hit = (rkeys[pos_c] == lkeys) & left.valid_mask() & (pos_c < r.count)

    cols: dict[str, jax.Array] = dict(left.columns)
    with stage("column_gather"):
        for name, col in r.columns.items():
            if name == key:
                continue
            tag = f"{name}_r" if name in left.columns else name
            cols[tag] = jnp.take(col, pos_c, axis=0, mode="clip")
    with stage("output_pack"):
        return Table(cols, left.count).filter(hit)


def join_sorted_expand(
    left: Table, right: Table, key: str, out_capacity: int
) -> Table:
    """General inner equi-join (many-to-many) with fixed output capacity.

    For each valid left row, the matching right range is [lo, hi) via double
    binary search; output slot j is mapped back to its (left row, offset)
    pair by searching the prefix-sum of match counts.  Rows beyond
    `out_capacity` are truncated (count reports the true total clamped).
    """
    l = sort_by_key(left, key)
    r = sort_by_key(right, key)
    sent = _key_sentinel(l.columns[key].dtype)
    lkeys = jnp.where(l.valid_mask(), l.columns[key], sent)
    rkeys = jnp.where(r.valid_mask(), r.columns[key], sent)
    rkeys_srch = jnp.where(jnp.arange(r.capacity) < r.count, rkeys, sent)

    lo = jnp.searchsorted(rkeys_srch, lkeys, side="left")
    hi = jnp.searchsorted(rkeys_srch, lkeys, side="right")
    hi = jnp.minimum(hi, r.count)
    lo = jnp.minimum(lo, hi)
    counts = jnp.where(l.valid_mask(), hi - lo, 0)
    ends = jnp.cumsum(counts)
    total = ends[-1] if counts.shape[0] else jnp.asarray(0, jnp.int32)

    slots = jnp.arange(out_capacity)
    li = jnp.searchsorted(ends, slots, side="right")
    li_c = jnp.clip(li, 0, left.capacity - 1)
    begin = ends[li_c] - counts[li_c]
    ri = lo[li_c] + (slots - begin)
    valid_out = slots < jnp.minimum(total, out_capacity)
    ri_c = jnp.clip(ri, 0, right.capacity - 1)

    cols: dict[str, jax.Array] = {}
    for name, col in l.columns.items():
        cols[name] = jnp.take(col, li_c, axis=0, mode="clip")
    for name, col in r.columns.items():
        if name == key:
            continue
        tag = f"{name}_r" if name in l.columns else name
        cols[tag] = jnp.take(col, ri_c, axis=0, mode="clip")
    out = Table(cols, jnp.minimum(total, out_capacity).astype(jnp.int32))
    # zero out padding rows for determinism
    mask = valid_out
    cols = {k: jnp.where(mask.reshape((-1,) + (1,) * (v.ndim - 1)), v, 0) for k, v in out.columns.items()}
    return Table(cols, out.count)
