"""Distributed operators: hash partition -> AllToAll shuffle -> local op.

Paper §III-D: "The experiments ... use the Distributed Join DataFrame
operator. For this case, the process follows: 1) Hash applicable columns into
partitioned tables, 2) Use AllToAll to send tables to the intended
destination, and 3) Execute a local join on the received tables."

Two surfaces, same algorithm:

- **sim_***: per-rank ``list[Table]`` through a :class:`Communicator` — the
  BSP/benchmark surface whose event log prices communication (any substrate).
  The communicator may be a ``CommSession`` root or a ``comm.split()``
  sub-group (one shuffle per mesh axis): the per-pair link table follows the
  group, so a shuffle whose group contains a hole-punch-failed pair is
  automatically priced at the relayed hybrid schedule while producing
  byte-identical rows (only the event log's timing differs — tested in
  test_session.py).
- ***_spmd**: inside ``shard_map`` over a mesh axis — the production path
  (direct ICI collectives), lowered and dry-run at pod scale.

The GroupBy combiner optimization (paper §IV-C: local pre-aggregation shrinks
50M rows to ~1e3 before the wire) is `combine=True`.

Compressed wire (`compress=True` on shuffle/join/groupby, both surfaces):
the shuffle is the communication-bound exchange (paper §IV), so each
(src, dst) block goes through the columnar codec in
``repro.dist.compression`` before the alltoallv.  Key columns are encoded
**exactly** (dictionary / narrow-width offsets / raw — never quantized), so
``hash(key) % P`` routing and join equality see bit-identical values;
float value columns ship as block-int8 with one f32 scale per block
(error bounded per block); integer value columns take the exact treatment,
keeping integer aggregates exact.  The communicator prices the event at the
compressed bytes and records the logical bytes in ``CommEvent.raw_bytes``.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.backends import direct
from repro.core.communicator import Communicator
from repro.dataframe import ops_local
from repro.dataframe.partition import build_partition_payload
from repro.dataframe.table import Table, from_stacked, stage
from repro.dist import compression


# ---------------------------------------------------------------------------
# Simulation surface (Communicator; used by BSP runtime + paper benchmarks)
# ---------------------------------------------------------------------------


def _shuffle_sim(
    tables: list[Table], key: str, comm: Communicator, compress: bool = False,
    algorithm: str | None = None,
) -> list[Table]:
    """Hash-shuffle each rank's table so rows land at hash(key) % P.

    ``compress=False`` ships every block as one float64 row-matrix (the
    historical wire format, 8 B/value) — note this silently loses integer
    precision above 2**53, so raw-path keys must stay within float64's
    exact-integer range.  ``compress=True`` runs each block through the
    columnar codec instead: the key column bit-exact at any magnitude,
    float value columns block-int8, integer value columns exact — and the
    communicator prices the compressed bytes while logging the raw ones.
    """
    p = comm.world_size
    names = sorted(tables[0].columns)
    if compress:
        return _shuffle_sim_compressed(tables, key, comm, names, algorithm=algorithm)
    sends: list[list[np.ndarray]] = []
    for t in tables:
        payload, counts = build_partition_payload(t, p, [key])
        row_mats = []
        for d in range(p):
            c = int(counts[d])
            row_mats.append(
                np.stack([np.asarray(payload[n][d][:c], dtype=np.float64) for n in names], axis=1)
                if c
                else np.zeros((0, len(names)))
            )
        sends.append(row_mats)
    recvs, _ = comm.alltoallv(sends, algorithm=algorithm)
    out: list[Table] = []
    for dst in range(p):
        rows = np.concatenate(recvs[dst], axis=0) if recvs[dst] else np.zeros((0, len(names)))
        data = {
            n: rows[:, i].astype(np.asarray(tables[0].columns[n]).dtype)
            for i, n in enumerate(names)
        }
        cap = max(1, sum(t.capacity for t in tables) // p * 2)
        out.append(Table.from_dict(data, capacity=max(cap, rows.shape[0])))
    return out


def _shuffle_sim_compressed(
    tables: list[Table], key: str, comm: Communicator, names: list[str],
    algorithm: str | None = None,
) -> list[Table]:
    """Codec-per-block variant of :func:`_shuffle_sim` (same row routing)."""
    p = comm.world_size
    dtypes = {n: np.asarray(tables[0].columns[n]).dtype for n in names}
    sends: list[list[compression.EncodedBlock]] = []
    for t in tables:
        payload, counts = build_partition_payload(t, p, [key])
        row = []
        for d in range(p):
            c = int(counts[d])
            cols = {n: np.asarray(payload[n][d][:c]) for n in names}
            row.append(compression.encode_block(cols, {key}))
        sends.append(row)
    recvs = comm.compressed_alltoallv(sends, algorithm=algorithm)
    out: list[Table] = []
    for dst in range(p):
        decoded = [compression.decode_block(b) for b in recvs[dst]]
        data = {
            n: np.concatenate([d[n] for d in decoded]).astype(dtypes[n])
            for n in names
        }
        nrows = data[names[0]].shape[0] if names else 0
        cap = max(1, sum(t.capacity for t in tables) // p * 2)
        out.append(Table.from_dict(data, capacity=max(cap, nrows)))
    return out


def sim_join(
    left: list[Table], right: list[Table], key: str, comm: Communicator,
    compress: bool = False, algorithm: str | None = None,
) -> list[Table]:
    """Distributed inner join (unique right keys) over the communicator.

    ``algorithm`` picks the collective schedule for every priced exchange
    (None -> the communicator's default, normally the tuned engine).
    """
    l_sh = _shuffle_sim(left, key, comm, compress=compress, algorithm=algorithm)
    r_sh = _shuffle_sim(right, key, comm, compress=compress, algorithm=algorithm)
    comm.barrier(algorithm=algorithm)
    return [ops_local.join_unique(l, r, key) for l, r in zip(l_sh, r_sh)]


def sim_groupby(
    tables: list[Table],
    key: str,
    aggs: dict[str, str],
    comm: Communicator,
    combine: bool = True,
    compress: bool = False,
    algorithm: str | None = None,
) -> list[Table]:
    """Distributed groupby; `combine` applies local pre-aggregation first."""
    work = tables
    final_aggs = dict(aggs)
    if combine:
        work = [_rename_back(ops_local.groupby_agg(t, key, aggs), aggs) for t in tables]
        # re-aggregating partials: sum-of-sums, max-of-maxes, sum-of-counts
        final_aggs = {c: ("sum" if op == "count" else op) for c, op in aggs.items()}
    shuffled = _shuffle_sim(work, key, comm, compress=compress, algorithm=algorithm)
    comm.barrier(algorithm=algorithm)
    out = [ops_local.groupby_agg(t, key, final_aggs) for t in shuffled]
    if combine:
        out = [_restore_names(t, aggs, final_aggs) for t in out]
    return out


def _rename_back(t: Table, aggs: dict[str, str]) -> Table:
    """groupby emits col_op names; map them back to col for the reduce step."""
    cols = {}
    for name, arr in t.columns.items():
        cols[name] = arr
    for col, op in aggs.items():
        cols[col] = cols.pop(f"{col}_{op}")
    return Table(cols, t.count)


def _restore_names(t: Table, aggs: dict[str, str], final_aggs: dict[str, str]) -> Table:
    """Normalize output names to the combine=False convention (col_origop)."""
    cols = dict(t.columns)
    for col, op in aggs.items():
        fop = final_aggs[col]
        if fop != op:
            cols[f"{col}_{op}"] = cols.pop(f"{col}_{fop}")
    return Table(cols, t.count)


# ---------------------------------------------------------------------------
# SPMD surface (shard_map; the production/dry-run path)
# ---------------------------------------------------------------------------


def shuffle_spmd(table: Table, key: str, axis: str, compress: bool = False) -> Table:
    """Hash-shuffle a per-shard table across mesh axis `axis`.

    Fixed-capacity alltoallv: send buffer is [P, cap_dest, ...] per shard.
    cap_dest = local capacity (worst-case skew absorbed by the receive pack).

    ``compress=True`` replaces each float *value* column's buffer with a
    block-int8 payload + per-block f32 scales across the alltoall (the key
    column and integer columns always ship exact — routing and join
    equality depend on them).
    """
    p = jax.lax.axis_size(axis)
    payload, counts = build_partition_payload(table, p, [key])
    with stage("exchange"):
        recv_counts = direct.alltoallv_counts(counts, axis)
        recv_payload = {}
        for name, buf in payload.items():
            if compress and name != key and jnp.issubdtype(buf.dtype, jnp.floating):
                q, scales = compression.quantize_slots(buf)
                q_r = direct.alltoall(q, axis, split_dim=0, concat_dim=0)
                s_r = direct.alltoall(scales, axis, split_dim=0, concat_dim=0)
                recv_payload[name] = compression.dequantize_slots(
                    q_r, s_r, buf.shape, buf.dtype
                )
            else:
                recv_payload[name] = direct.alltoall(buf, axis, split_dim=0, concat_dim=0)
    return from_stacked(recv_payload, recv_counts)


def join_spmd(
    left: Table, right: Table, key: str, axis: str, compress: bool = False
) -> Table:
    l_sh = shuffle_spmd(left, key, axis, compress=compress)
    r_sh = shuffle_spmd(right, key, axis, compress=compress)
    return ops_local.join_unique(l_sh, r_sh, key)


def groupby_spmd(
    table: Table, key: str, aggs: dict[str, str], axis: str,
    combine: bool = True, compress: bool = False,
) -> Table:
    work = table
    final_aggs = dict(aggs)
    if combine:
        work = _rename_back(ops_local.groupby_agg(table, key, aggs), aggs)
        final_aggs = {c: ("sum" if op == "count" else op) for c, op in aggs.items()}
    shuffled = shuffle_spmd(work, key, axis, compress=compress)
    out = ops_local.groupby_agg(shuffled, key, final_aggs)
    if combine:
        out = _restore_names(out, aggs, final_aggs)
    return out
