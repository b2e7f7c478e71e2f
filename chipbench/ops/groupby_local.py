"""Operator ``groupby_local``: the paper's local groupby on one worker.

``ops_local.groupby_agg`` under ``jax.jit`` on one chip: sort the table by
key, then reduce each key's segment by the configured aggregates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from chipbench import groupbyref
from repro.dataframe import Table, ops_local


def inputs(config: dict, devices: list, seed: int) -> tuple[Table]:
    """The table, made on the device from `seed`."""
    if config["workers"] != 1 or len(devices) != 1:
        raise ValueError("groupby_local runs one worker on one device")
    gen = jax.jit(lambda key: tuple(x.reshape(-1) for x in groupbyref.generate(config, key)),
                  out_shardings=SingleDeviceSharding(devices[0]))
    cols = gen(groupbyref.seed_key(seed))
    count = jax.device_put(jnp.int32(config["rows_per_worker"]), devices[0])
    return (Table(dict(zip(config["columns"], cols)), count),)


def program(config: dict, devices: list):
    """The call into the system under test; jitted by the harness."""
    return lambda table: ops_local.groupby_agg(table, config["key"], config["aggs"])


def row_count(out: Table) -> jax.Array:
    return out.count


def reference(config: dict, args: tuple[Table]) -> tuple[np.ndarray, ...]:
    """The plain reference's answer, from host copies of the input."""
    (table,) = args
    return groupbyref.reference(table.to_numpy(), config["key"], config["aggs"])


def output(config: dict, out: Table) -> tuple[np.ndarray, ...]:
    """(key, then each aggregate) of the output's valid rows, on the host."""
    cols = out.to_numpy()
    return (cols[config["key"]],) + tuple(cols[f"{c}_{op}"] for c, op in config["aggs"].items())


rows_per_call = groupbyref.rows_per_call
expected_rows = groupbyref.expected_rows
wrong_rows = groupbyref.wrong_rows
essential_bytes = groupbyref.essential_bytes
