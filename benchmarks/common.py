"""Shared benchmark utilities: timing + the execution-time composition model.

Every scaling benchmark composes, per DESIGN.md §2:

    T(world) = T_init(world) + T_datagen + T_local(measured here, rescaled)
               + T_comm(priced event log)

T_local is REALLY measured: the actual distributed-join/groupby algorithm
runs on this host at `SCALE`-reduced row counts and is extrapolated linearly
in rows (verified ~linear in `test_benchmarks.py`); T_comm comes from the
calibrated channel models; T_init from the NAT/bootstrap model.
"""

from __future__ import annotations

import time

import numpy as np
import jax

from repro.core import netsim
from repro.dataframe import Table, ops_local

SCALE = 100  # row-count reduction vs the paper's experiment (CPU host)
WORLDS = (1, 2, 4, 8, 16, 32, 64)
ITERATIONS = 10  # paper: ten iterations per trial


def time_call(fn, *args, repeat: int = 3, **kw) -> float:
    """Median wall seconds of fn(*args) with jax sync."""
    outs = fn(*args, **kw)
    jax.block_until_ready(outs)  # warmup/compile
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def join_columns(rows: int, seed: int = 0) -> tuple[dict, dict]:
    """The paper's microbenchmark data as NumPy columns: two tables of
    `rows` rows, unique integer keys drawn from [0, 2 * rows)."""
    rng = np.random.default_rng(seed)
    left = {"k": rng.permutation(rows * 2)[:rows].astype(np.int32),
            "v": rng.integers(0, 1 << 20, rows).astype(np.int32)}
    right = {"k": rng.permutation(rows * 2)[:rows].astype(np.int32),
             "w": rng.integers(0, 1 << 20, rows).astype(np.int32)}
    return left, right


def join_capacity(rows: int, cap_slack: float = 1.1) -> int:
    return int(rows * cap_slack) + 8


def gen_join_tables(rows: int, seed: int = 0, cap_slack: float = 1.1):
    """The paper's microbenchmark data: two tables, ~unique integer keys."""
    left, right = join_columns(rows, seed)
    cap = join_capacity(rows, cap_slack)
    return Table.from_dict(left, capacity=cap), Table.from_dict(right, capacity=cap)


def measure_local_join_seconds(rows: int) -> float:
    """Measured single-worker join time at `rows` (jit'd, synced)."""
    left, right = gen_join_tables(rows)
    fn = jax.jit(lambda l, r: ops_local.join_unique(l, r, "k").count)
    return time_call(fn, left, right)


def measure_local_groupby_seconds(rows: int, ngroups: int = 1000) -> float:
    rng = np.random.default_rng(1)
    t = Table.from_dict(
        {"k": rng.integers(0, ngroups, rows).astype(np.int32),
         "v": rng.integers(0, 100, rows).astype(np.int32)},
    )
    fn = jax.jit(lambda t: ops_local.groupby_agg(t, "k", {"v": "sum"}).count)
    return time_call(fn, t)


def join_time_model(
    platform: netsim.PlatformModel,
    world: int,
    rows_total: int,
    weak: bool,
    local_s_per_row: float,
    datagen_s_per_row: float,
    iterations: int = ITERATIONS,
) -> dict:
    """Compose one experiment's wall time (paper Table II/III rows)."""
    rows_per_worker = rows_total if weak else max(rows_total // world, 1)
    core_eff = min(platform.cores, 4) ** 0.5  # partial intra-worker parallelism
    local = local_s_per_row * rows_per_worker / platform.cpu_speed / core_eff
    datagen = datagen_s_per_row * rows_per_worker / platform.cpu_speed
    per_rank_bytes = rows_per_worker * 2 * 16  # two tables x 16B/row on the wire
    comm = sum(
        netsim.collective_time(platform.channel, "alltoallv", world, per_rank_bytes)
        + netsim.collective_time(platform.channel, "barrier", world, 0)
        for _ in range(iterations)
    ) if world > 1 else 0.0
    sched = platform.sched_jitter_s * (np.log2(world) if world > 1 else 0.0)
    init = platform.init_time(world)
    total = init + datagen + local * iterations + comm + sched
    return {
        "world": world,
        "init_s": init,
        "datagen_s": datagen,
        "local_s": local * iterations,
        "comm_s": comm,
        "sched_s": sched,
        "total_s": total,
    }
