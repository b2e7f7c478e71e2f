"""Device time by stage (``chipbench/stages.py`` and its readers), on the CPU.

A synthetic program text and window check the attribution rule and each
reader; the operators compiled here check that their stage names reach the
compiled program; the chip recordings in ``testdata/`` check the whole
reduction on traces of the program as it runs.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import devtrace, stages  # noqa: E402
from repro.dataframe import STAGES, Table, ops_local  # noqa: E402

READERS = ("probe_ms", "output_pack_ms", "partition_ms", "receive_pack_ms",
           "exchange_wait_ms")
TESTDATA = ROOT / "chipbench" / "testdata"


def _read(metric: str, ctx: devtrace.Context) -> float | None:
    return importlib.import_module(f"chipbench.metrics.{metric}").read(ctx)


def test_stage_names_are_no_jax_primitive():
    from jax.extend.core import primitives

    prims = {getattr(p, "name", None) for p in vars(primitives).values()}
    assert {"sort", "gather", "scatter", "all_to_all"} <= prims
    assert len(set(STAGES)) == len(STAGES) and not set(STAGES) & prims
    assert stages.STAGES == STAGES


# ---------------------------------------------------------------------------
# the attribution rule on a synthetic program and window
# ---------------------------------------------------------------------------

HLO = textwrap.dedent("""\
    HloModule jit_f, is_scheduled=true

    FileNames
    1 "f.py"

    %body (p: (s32[], s32[8])) -> (s32[], s32[8]) {
      %fusion.1 = s32[8]{0} fusion(%gte), kind=kLoop, calls=%fc.1, metadata={op_name="jit(f)/probe/jit(searchsorted)/vmap()/while/body/closed_call/gather" stack_frame_id=4}
    }

    %fc.11 (p: s32[8]) -> s32[8] {
      %transpose.1 = s32[8]{0} transpose(%p), dimensions={0}, metadata={op_name="jit(f)/shard_map/partition_pack/gather"}
      ROOT %scatter.2 = s32[8]{0} scatter(%p, %transpose.1), to_apply=%add
    }

    %fc.12 (p: s32[8]) -> s32[8] {
      %gather.3 = s32[8]{0} gather(%p, %p), metadata={op_name="jit(f)/probe/gather"}
      ROOT %gather.4 = s32[8]{0} gather(%gather.3, %p), metadata={op_name="jit(f)/output_pack/gather"}
    }

    ENTRY %main (x: s32[8]) -> s32[8] {
      %fusion.11 = s32[8]{0} fusion(%x), kind=kCustom, calls=%fc.11, backend_config={"a":"b"}
      %fusion.12 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fc.12
      %sort.2 = (s32[8]{0}, s32[8]{0}) sort(%x, %iota), dimensions={0}, metadata={op_name="jit(f)/output_pack/key_sort/jit(argsort)/sort" stack_frame_id=2}
      %fusion.3 = s32[8]{0} fusion(%sort.2), kind=kLoop, metadata={op_name="jit(f)/column_gather/probe" stack_frame_id=3}, backend_config={"a":"b"}
      %copy.4 = s32[8]{0} copy(%fusion.3), backend_config={"flag_configs":[]}
      %while.5 = (s32[], s32[8]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/probe/jit(searchsorted)/vmap()/while"}
      %fusion.6 = u32[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(f)/shard_map/partition_hash/mul"}
      %fusion.7 = s32[4,2]{1,0} fusion(%x), kind=kLoop, metadata={op_name="jit(f)/shard_map/partition_pack/scatter"}
      %all_to_all.8 = s32[4,2]{1,0} all-to-all(%fusion.7), dimensions={0}, metadata={op_name="jit(f)/shard_map/exchange/all_to_all"}
      %all_to_all.9 = s32[4,1]{1,0} all-to-all(%c), dimensions={0}, metadata={op_name="jit(f)/shard_map/all_to_all"}
      ROOT %fusion.10 = s32[8]{0} fusion(%all_to_all.8), kind=kLoop, metadata={op_name="jit(f)/receive_pack/jit(_take)/gather" source_file="t.py"}
    }
    """)


@pytest.mark.parametrize("name,stage", [
    ("fusion.1", "probe"),              # a fusion of a while's body
    ("sort.2", "key_sort"),             # the innermost stage wins
    ("fusion.3", "column_gather"),      # the last component is the primitive, never a stage
    ("copy.4", stages.UNATTRIBUTED),    # no metadata
    ("while.5", "probe"),
    ("fusion.6", "partition_hash"),
    ("all_to_all.8", "exchange"),
    ("all_to_all.9", stages.UNATTRIBUTED),  # metadata, but no stage
    ("fusion.10", "receive_pack"),      # the root of the entry computation
    ("scatter.2", stages.UNATTRIBUTED),
    ("fusion.11", "partition_pack"),    # no metadata: the one stage its computation names
    ("fusion.12", stages.UNATTRIBUTED),  # no metadata, and its computation names two
])
def test_each_instruction_is_attributed_to_its_innermost_stage(name, stage):
    assert stages.instruction_stages(HLO)[name] == stage


A2A = (((500, 600), (520, 600), (560, 600)), ((800, 850), (820, 850), (810, 850)))


def _three_chips(a2a=A2A) -> devtrace.Summary:
    """Three chips, two calls in a 1000 ns window.  Chip 0 runs every stage;
    each chip runs the instances of the exchange's all-to-all given as (start,
    end) per chip, and one of an all-to-all of no stage."""
    chip0 = [("fusion.6", "fusion", 0, 30, True), ("fusion.7", "fusion", 30, 80, True),
             ("while.5", "while", 100, 300, False), ("fusion.1", "fusion", 120, 220, True),
             ("sort.2", "sort", 300, 350, True), ("fusion.3", "fusion", 350, 370, True),
             ("copy.4", "copy", 370, 380, True), ("fusion.10", "fusion", 380, 440, True),
             ("unknown.11", "fusion", 440, 450, True)]
    ops = {}
    for d in range(3):
        events = [("all_to_all.8", "all-to-all", *inst[d], True) for inst in a2a]
        events.append(("all_to_all.9", "all-to-all", 900 + 10 * d, 950, True))
        ops[f"/device:TPU:{d}"] = (chip0 if d == 0 else []) + events
    return devtrace.Summary((0, 1000), 2, ops, [("call", 0, 500), ("call", 500, 1000)])


def test_stage_time_sums_leaves_per_chip():
    # the while (200 ns) holds fusion.1 (100 ns): only the fusion counts
    got = stages.stage_ns(_three_chips(), HLO)["/device:TPU:0"]
    assert got == {"partition_hash": 30, "partition_pack": 50, "probe": 100,
                   "key_sort": 50, "column_gather": 20, "receive_pack": 60,
                   "exchange": 100 + 50, stages.UNATTRIBUTED: 10 + 10 + 50}
    assert stages.stage_ns(_three_chips(), HLO)["/device:TPU:1"] == {
        "exchange": 80 + 30, stages.UNATTRIBUTED: 40}


@pytest.mark.parametrize("metric,ns", [("probe_ms", 100), ("output_pack_ms", None),
                                       ("partition_ms", 80), ("receive_pack_ms", 60),
                                       ("exchange_wait_ms", 60 + 20)])
def test_readers_on_a_synthetic_window(metric, ns):
    ctx = devtrace.Context(_three_chips(), HLO, 10, {}, {})
    want = None if ns is None else pytest.approx(ns / 2 / 1e6)
    assert _read(metric, ctx) == want


@pytest.mark.parametrize("metric", READERS)
def test_readers_find_nothing_in_a_program_without_stage_names(metric):
    assert _read(metric, devtrace.Context(_three_chips(), "", 10, {}, {})) is None
    unscoped = HLO
    for st in STAGES:
        unscoped = unscoped.replace(f"/{st}/", "/")
    assert _read(metric, devtrace.Context(_three_chips(), unscoped, 10, {}, {})) is None


def test_readers_find_nothing_where_the_program_has_no_stage_vocabulary(monkeypatch):
    # a program from before the stage names: repro.dataframe has no STAGES
    monkeypatch.setattr(stages, "STAGES", ())
    ctx = devtrace.Context(_three_chips(), HLO, 10, {}, {})
    assert [_read(m, ctx) for m in READERS] == [None] * len(READERS)


@pytest.mark.parametrize("a2a,waits", [
    ((((500, 600),) * 3, ((800, 850),) * 3), (0, 0, 0)),
    (A2A, (60 + 20, 40 + 0, 0 + 10)),
    # chip 0 leaves its first instance before the last chip enters it: its
    # wait is the whole of its time there
    ((((500, 540), (560, 600), (500, 600)), ((800, 850), (800, 850), (845, 850))),
     (40 + 45, 0 + 45, 60 + 0)),
])
def test_exchange_wait_per_chip_from_offset_starts(a2a, waits):
    s = _three_chips(a2a)
    exchange = lambda n, c: stages.instruction_stages(HLO).get(n) == "exchange"  # noqa: E731
    got = stages.collective_wait_ns(s, exchange)
    assert [got[f"/device:TPU:{d}"] for d in range(3)] == list(waits)
    ctx = devtrace.Context(s, HLO, 10, {}, {})
    assert _read("exchange_wait_ms", ctx) == pytest.approx(max(waits) / 2 / 1e6)


# ---------------------------------------------------------------------------
# the operators, compiled, name every stage they run
# ---------------------------------------------------------------------------

JOIN_STAGES = {"key_sort", "probe", "column_gather", "output_pack"}
SHUFFLE_STAGES = {"partition_hash", "partition_pack", "exchange", "receive_pack"}


def _named(compiled) -> set[str]:
    return set(stages.instruction_stages(compiled.as_text()).values()) - {stages.UNATTRIBUTED}


def _table(n: int, *names: str) -> Table:
    return Table({c: jnp.arange(n, dtype=jnp.int32) for c in names}, jnp.int32(n - 3))


@pytest.mark.parametrize("op,want", [
    (lambda t: ops_local.join_unique(t, t.project(["k"]).with_column("w", t.columns["v"]), "k"),
     JOIN_STAGES),
    (lambda t: ops_local.groupby_agg(t, "k", {"v": "sum"}), {"key_sort", "segment_reduce"}),
])
def test_local_operator_names_its_stages(op, want):
    assert _named(jax.jit(op).lower(_table(64, "k", "v")).compile()) == want


SPMD_PROGRAM = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from chipbench import stages
    from repro.dataframe import Table, ops_dist
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",))
    progs = {{
        "join_spmd": lambda k, v, c: ops_dist.join_spmd(
            Table({{"k": k, "v": v}}, c[0]), Table({{"k": k, "w": v}}, c[0]), "k", "data"),
        "groupby_spmd": lambda k, v, c: ops_dist.groupby_spmd(
            Table({{"k": k, "v": v}}, c[0]), "k", {{"v": "sum"}}, "data"),
    }}
    col = jnp.arange(4 * 64, dtype=jnp.int32)
    counts = jnp.full((4,), 60, jnp.int32)
    out = {{}}
    for name, body in progs.items():
        f = jax.shard_map(lambda k, v, c: [x.reshape(-1) for x in jax.tree.leaves(body(k, v, c))],
                          mesh=mesh,
                          in_specs=(P("data"),) * 3, out_specs=P("data"))
        hlo = jax.jit(f).lower(col, col, counts).compile().as_text()
        out[name] = sorted(set(stages.instruction_stages(hlo).values()) - {{stages.UNATTRIBUTED}})
    print("RESULT", json.dumps(out))
""")


@pytest.fixture(scope="module")
def spmd_stages() -> dict:
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", SPMD_PROGRAM.format(root=str(ROOT), src=str(ROOT / "src"))],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.split("RESULT ", 1)[1])


@pytest.mark.parametrize("program,want", [
    ("join_spmd", SHUFFLE_STAGES | JOIN_STAGES),
    ("groupby_spmd", SHUFFLE_STAGES | {"key_sort", "segment_reduce"}),
])
def test_spmd_operator_on_four_cpu_devices_names_its_stages(spmd_stages, program, want):
    assert set(spmd_stages[program]) == want


# ---------------------------------------------------------------------------
# recordings of the scoped program on the chip
# ---------------------------------------------------------------------------


def _recording(name: str) -> devtrace.Context:
    s = devtrace.summarize(TESTDATA / f"{name}.xplane.pb")
    return devtrace.Context(s, (TESTDATA / f"{name}.hlo.txt").read_text(), 20000, {}, {})


@pytest.mark.parametrize("name,chips", [("join_local_stages", 1), ("join_spmd_stages", 4)])
def test_recorded_chip_trace_reduces_to_the_stage_metrics(name, chips):
    # join_local and join_spmd at 20,000 rows per worker per table, 10 calls
    ctx = _recording(name)
    assert (len(ctx.summary.ops), ctx.summary.calls) == (chips, 10)
    assert _read("probe_ms", ctx) > 0 and _read("output_pack_ms", ctx) > 0
    for dev, by_stage in stages.stage_ns(ctx.summary, ctx.hlo).items():
        named = sum(t for st, t in by_stage.items() if st != stages.UNATTRIBUTED)
        assert named >= 0.95 * devtrace.busy_ns(ctx.summary, dev), (dev, by_stage)
    four = ("partition_ms", "receive_pack_ms", "exchange_wait_ms")
    if chips == 4:
        assert all(_read(m, ctx) is not None for m in four)
    else:
        assert [_read(m, ctx) for m in four] == [None] * 3
