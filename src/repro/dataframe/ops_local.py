"""Local (single-partition) relational operators.

These are the "local join on the received tables" / local aggregation halves
of the paper's distributed operators.  All are jit-safe over fixed-capacity
tables; variable-size results use capacity + count + packing.

Algorithms are TPU-minded: sort-based (argsort lowers to a bitonic network on
TPU), sort-merge probes that co-sort both key sets and read the matches off
cumulative scans, with no random gather, and segment reductions as segmented
scans over the key-sorted rows, compacted by one sort, with no scatter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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


_SCAN_WIDTH = 128  # one TPU lane row


def _inclusive_scan(x: jax.Array, op, identity: int) -> jax.Array:
    """Inclusive scan of 1-D `x` under `op` (``lax.add``, ``lax.max``).

    Blocked in rows of 128: a windowed reduction along each row, then the
    scan of the row totals (recursively) added back.  The TPU compiler
    rewrites a 1-D windowed reduction into this same form but drops the op
    metadata, and with it the stage name; written out, every op keeps it.
    """
    n = x.shape[0]
    if n == 0:
        return x
    w = min(n, _SCAN_WIDTH)
    rows = -(-n // w)
    x2 = jnp.pad(x, (0, rows * w - n), constant_values=identity).reshape(rows, w)
    x2 = lax.reduce_window(x2, x.dtype.type(identity), op, (1, w), (1, 1), ((0, 0), (w - 1, 0)))
    if rows > 1:
        carry = _inclusive_scan(x2[:, -1], op, identity)
        x2 = op(x2, jnp.pad(carry[:-1], (1, 0), constant_values=identity)[:, None])
    return x2.reshape(-1)[:n]


def _segmented_scan(x: jax.Array, seg: jax.Array, op) -> jax.Array:
    """Inclusive scan of 1-D `x` under the associative `op`, restarted wherever
    the segment id changes; `seg` holds each row's id, and each segment's rows
    are contiguous.

    Blocked in rows of 128, as ``_inclusive_scan``: inside a row, log2(128)
    doubling steps, each folding in the partial of the row 2^i lanes back if it
    lies in the same segment; then the scan of the row ends (recursively) is
    folded into the lanes that continue the previous row's last segment.  Each
    step combines two adjacent runs of one segment, the earlier first, so the
    result is exact for any associative `op`, with no identity, gather or
    scatter.
    """
    n = x.shape[0]
    if n == 0:
        return x
    w = min(n, _SCAN_WIDTH)
    rows = -(-n // w)
    x2 = jnp.pad(x, (0, rows * w - n)).reshape(rows, w)
    s2 = jnp.pad(seg, (0, rows * w - n)).reshape(rows, w)
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    d = 1
    while d < w:
        back = ((0, 0), (d, 0))
        same = (lane >= d) & (jnp.pad(s2[:, :-d], back) == s2)
        x2 = jnp.where(same, op(jnp.pad(x2[:, :-d], back), x2), x2)
        d *= 2
    if rows > 1:
        ends = _segmented_scan(x2[:, -1], s2[:, -1], op)
        row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        same = (row > 0) & (jnp.pad(s2[:-1, -1], (1, 0))[:, None] == s2)
        x2 = jnp.where(same, op(jnp.pad(ends[:-1], (1, 0))[:, None], x2), x2)
    return x2.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# GroupBy (paper §IV-C) — sort + segmented scans, with combiner support
# ---------------------------------------------------------------------------

_AGG_OPS = {"sum": lax.add, "max": lax.max, "min": lax.min, "count": lax.add}


def groupby_agg(table: Table, key: str, aggs: dict[str, str]) -> Table:
    """Group by integer `key`; aggregate value columns by op name.

    The rows are sorted by key; each aggregate is a segmented scan over them,
    which holds the segment's aggregate at its last row (``count`` scans ones),
    and one sort on the segment id of those last rows packs them, in key order,
    to the front.  The reduction is exact at every integer width and does no
    scatter.

    Output: one row per distinct key (packed), capacity preserved, padding
    zeroed.  `aggs` maps value-column name -> op name.  The mean op is
    expressed by the caller as sum+count (associativity needed for the
    distributed combiner).
    """
    for op in aggs.values():
        if op not in _AGG_OPS:
            raise ValueError(f"unsupported agg {op!r}; have {sorted(_AGG_OPS)}")
    t = sort_by_key(table, key)
    with stage("segment_reduce"):
        keys = t.columns[key]
        valid = t.valid_mask()
        sent = _key_sentinel(keys.dtype)
        keys_m = jnp.where(valid, keys, sent)
        cap = table.capacity

        # Segment ids: 0-based rank of each distinct key in sorted order; invalid
        # rows form one trailing segment `cap`, sorted away below.
        new_seg = jnp.concatenate(
            [jnp.ones((1,), jnp.int32), (keys_m[1:] != keys_m[:-1]).astype(jnp.int32)]
        )
        new_seg = jnp.where(valid, new_seg, 0)
        seg = jnp.where(valid, _inclusive_scan(new_seg, lax.add, 0) - 1, cap)
        n_groups = jnp.sum(new_seg).astype(jnp.int32)
        # a segment's last row: valid, and the next row starts a segment or is padding
        last = valid & jnp.concatenate([(new_seg[1:] == 1) | ~valid[1:], jnp.ones((1,), bool)])

        out_cols: dict[str, jax.Array] = {key: keys}  # every row of a segment holds its key
        for col, op in aggs.items():
            vals = jnp.ones((cap,), jnp.int32) if op == "count" else t.columns[col]
            out_cols[f"{col}_{op}"] = _segmented_scan(vals, seg, _AGG_OPS[op])

        _, *packed = lax.sort((jnp.where(last, seg, cap), *out_cols.values()), num_keys=1,
                              is_stable=False)
        mask = jnp.arange(cap) < n_groups
        return Table({name: jnp.where(mask, col, 0) for name, col in zip(out_cols, packed)},
                     n_groups)


# ---------------------------------------------------------------------------
# Joins
# ---------------------------------------------------------------------------


def _merge_probe(rkeys: jax.Array, r_valid: jax.Array, lkeys: jax.Array):
    """Lower bound of every left key in the sorted valid right keys, and
    whether it hits, in left row order, without a gather, a scatter or a loop.

    Both key sets are sorted together on (key, code): a valid right row's code
    is -1, a left row's its row index, right padding's nl.  So inside one key's
    run a valid right row comes first, then the left rows, then padding: a
    valid key equal to the dtype's max still matches, and padding never does.
    In that order a left slot's lower bound is the count of right rows before
    it, and it hits iff a right row lies between its run's head and itself.
    A second sort, on the code, puts the left slots back in row order.  Every
    index and scan is int32 (x64 would make them int64); capacities fit it.
    """
    i32 = jnp.int32
    nl = lkeys.shape[0]
    code = jnp.concatenate([jnp.where(r_valid, i32(-1), i32(nl)), jnp.arange(nl, dtype=i32)])
    keys, code = lax.sort((jnp.concatenate([rkeys, lkeys]), code), num_keys=2, is_stable=False)
    is_r = (code < 0).astype(i32)
    n_r = _inclusive_scan(is_r, lax.add, 0)  # right rows up to and including each slot
    head = jnp.concatenate([jnp.ones((1,), bool), keys[1:] != keys[:-1]])
    run_start = _inclusive_scan(jnp.where(head, n_r - is_r, 0), lax.max, 0)  # right rows before
    hit = (code >= 0) & (n_r > run_start)
    # the lower bound (n_r, less the hit row itself) in the high bits, the hit in bit 0
    packed = 2 * n_r - hit
    _, packed = lax.sort((jnp.where(code < 0, i32(nl), code), packed), num_keys=1,
                         is_stable=False)
    packed = packed[:nl]
    return packed >> 1, (packed & 1).astype(bool)


def join_unique(left: Table, right: Table, key: str, how: str = "inner") -> Table:
    """Equi-join where `right` has at most one valid row per key.

    Sort-merge probe: sort right by key, co-sort its keys with the left keys
    to find each left key's match (``_merge_probe``).  This is the paper's
    microbenchmark regime (uniform random ~unique keys).  Inner join only
    here; unmatched left rows are dropped (packed out).
    """
    if how != "inner":
        raise NotImplementedError("join_unique supports inner joins")
    r = sort_by_key(right, key)
    with stage("probe"):
        pos, hit = _merge_probe(r.columns[key], r.valid_mask(), left.columns[key])
        pos_c = jnp.minimum(pos, right.capacity - 1)
        hit = hit & left.valid_mask()

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
