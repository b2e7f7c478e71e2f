"""Distributed Memory Dataframe (DDMF) — the Cylon analogue (paper §III-A).

A dataframe here is a fixed-capacity columnar table: a pytree of equally
sized jnp arrays plus a valid-row count (XLA requires static shapes; padding
plus masking replaces Arrow's ragged buffers).  The distributed form is P
such tables, one per mesh shard — exactly the paper's "collection of P
dataframes or partitions of lengths {N_0..N_{P-1}}".
"""

from repro.dataframe.table import STAGES, Schema, Table, stage  # noqa: F401
from repro.dataframe.partition import hash32, hash_columns, build_partition_payload  # noqa: F401
from repro.dataframe import io, ops_local, ops_dist, tensor  # noqa: F401
