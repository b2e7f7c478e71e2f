"""The groupby cell's yardstick, operator and readers, checked on the CPU.

``groupbyref`` (generator, reference, comparison, control, essential bytes)
at small sizes with 64-bit types and seeded data; whole runs of the cell
through ``run.run_cell`` at a tiny ``rows_per_worker``, sound and with the
timed program broken; and the stage readers on a recording of the operator
on the chip (``testdata/groupby_local_stages``).
"""

from __future__ import annotations

import importlib
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import devtrace, groupby_control, groupbyref, run, stages  # noqa: E402
from repro.dataframe import Table, ops_local  # noqa: E402

CELL = "groupby-50m-1chip"
TESTDATA = ROOT / "chipbench" / "testdata"
AGGS = {"a": "sum", "b": "max"}
TINY_ROWS = 3000


def tiny_cell(rows: int = TINY_ROWS):
    manifest, cell, config, traffic = run.load_cell(CELL)
    return manifest, cell, dict(config, rows_per_worker=rows), traffic


def run_tiny(devices, seed: int = 3, seconds: float = 0.2) -> dict:
    return run.run_cell(*tiny_cell(), devices, seed, seconds, trace=False,
                        start=time.perf_counter())


def _read(metric: str, ctx: devtrace.Context) -> float | None:
    return importlib.import_module(f"chipbench.metrics.{metric}").read(ctx)


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,groups,rows,capacity", [
    (1, 1, 100, 128),
    (2, 7, 1000, 1100),
    (3, 1000, 5000, 5508),
    (4, 300, 129, 4096),     # the segment-id scan spans several rows of 128
    (5, 50, 0, 64),          # no valid row
    (6, 5000, 5000, 5000),   # every slot valid, nearly every key its own group
    (7, 5, 0, 0),            # no slot at all
])
def test_groupby_agg_equals_the_reference(seed, groups, rows, capacity):
    rng = np.random.default_rng(seed)
    keys = rng.integers(-2**63, 2**63 - 1, groups, dtype=np.int64, endpoint=True)
    cols = {"k": keys[rng.integers(0, groups, capacity)],
            "a": rng.integers(0, 2**40, capacity, dtype=np.int64),
            "b": rng.integers(-2**63, 2**63 - 1, capacity, dtype=np.int64, endpoint=True)}
    want = groupbyref.reference({c: v[:rows] for c, v in cols.items()}, "k", AGGS)
    with jax.enable_x64(True):
        table = Table({c: jnp.asarray(v) for c, v in cols.items()}, jnp.int32(rows))
        out = jax.jit(lambda t: ops_local.groupby_agg(t, "k", AGGS))(table)
        got = out.to_numpy()
        assert out.capacity == capacity and int(out.count) == len(want[0])
    assert groupbyref.wrong_rows((got["k"], got["a_sum"], got["b_max"]), want) == 0


def test_wrong_rows_counts_the_symmetric_difference():
    want = (np.array([1, 4, 7]), np.array([10, 40, 70]), np.array([5, 6, 7]))
    assert groupbyref.wrong_rows(want, want) == 0
    assert groupbyref.wrong_rows(tuple(x[[2, 0, 1]] for x in want), want) == 0
    assert groupbyref.wrong_rows((want[0], want[1], np.array([5, 6, 8])), want) == 2
    assert groupbyref.wrong_rows(tuple(x[:2] for x in want), want) == 1
    assert groupbyref.wrong_rows(tuple(np.concatenate([x, x[:1]]) for x in want), want) == 1
    assert groupbyref.wrong_rows((np.array([9]),) * 3, want) == 4  # a key not in want
    assert groupbyref.wrong_rows((np.array([2]),) * 3, tuple(x[:0] for x in want)) == 1


def test_generated_keys_number_exactly_groups_and_are_distinct():
    _, _, config, _ = tiny_cell(20_000)
    with jax.enable_x64(True):
        keys = np.asarray(groupbyref.distinct_keys(groupbyref.seed_key(2**40 + 9), 1000))
        cols = groupby_control.host_columns(config, 2**40 + 9)
    assert keys.dtype == np.int64 and len(np.unique(keys)) == 1000
    assert keys.min() >= 0 and keys.max() >= 2**62  # over [0, 2**63)
    # 20 rows a group: every key is drawn
    assert len(np.unique(cols["k"])) == 1000 and cols["k"].min() >= 0
    assert 2**39 <= cols["a"].max() < 2**40 and cols["a"].min() >= 0
    assert cols["b"].max() >= 2**62 and cols["b"].min() >= 0
    assert list(cols) == ["k", "a", "b"] and {v.dtype for v in cols.values()} == {
        np.dtype("int64")}


def test_columns_need_the_configured_64_bit_types():
    _, _, config, _ = tiny_cell()
    with pytest.raises(ValueError, match="jax_enable_x64"):
        groupbyref.generate(config, groupbyref.seed_key(1))
    with pytest.raises(ValueError, match="row_bytes"):
        groupbyref.check(dict(config, row_bytes=16))
    with pytest.raises(ValueError, match="aggregates"):
        groupbyref.check(dict(config, aggs={"a": "sum", "b": "mean"}))


def test_control_fails_the_check_that_the_reference_passes():
    _, _, config, _ = tiny_cell()
    r = groupby_control.readings(config, seed=7)
    assert r["reference_wrong_rows"] == 0 <= r["limit"]
    assert r["control_wrong_rows"] == 2 * r["rows_out"] > r["limit"]


def test_control_differs_from_the_reference_in_every_group():
    _, _, config, _ = tiny_cell(20_000)
    with jax.enable_x64(True):
        cols = groupby_control.host_columns(config, 11)
    want = groupbyref.reference(cols, "k", AGGS)
    got = groupbyref.control(cols, "k", AGGS)
    assert len(want[0]) == len(got[0]) == config["groups"]
    # the control's group of each reference key is the one of its narrowed key
    at = np.searchsorted(got[0], want[0].astype(np.int32).astype(np.int64))
    np.testing.assert_array_equal(got[0][at], want[0].astype(np.int32))
    for g, w in zip((got[0][at], got[1][at], got[2][at]), want):
        assert np.all(g != w)


def test_essential_bytes_from_shapes():
    _, _, config, _ = tiny_cell(50_000_000)
    want = (np.zeros(1000),)
    e = groupbyref.essential_bytes(config, want)
    assert e == {"hbm": 50_000_000 * 24 + 1000 * 24, "ici": 0.0}
    assert e["hbm"] / 819e9 == pytest.approx(1.465e-3, rel=1e-3)  # ms at v5e HBM peak
    w4 = groupbyref.essential_bytes(dict(config, workers=4), want)
    assert w4 == {"hbm": 50_000_000 * 24 + 1000 * 24 / 4, "ici": 1000 * 24 * 3 / 4}
    assert groupbyref.rows_per_call(config) == 50_000_000
    assert groupbyref.capacity(config) == 55_000_008


# ---------------------------------------------------------------------------
# whole runs of the cell at a tiny size, sound and with the timed path broken
# ---------------------------------------------------------------------------


def test_groupby_local_run_is_correct_at_a_tiny_size():
    res = run_tiny(jax.devices()[:1], seed=2**33 + 1)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"rows_per_s", "op_p95_ms", "setup_s"}
    assert res["checks"]["wrong_rows"]["value"] == 0
    assert list(res)[-1] == "checks"


def _faults():
    def altered(config, devices):
        def f(t):
            out = ops_local.groupby_agg(t, "k", AGGS)
            return out.with_column("a_sum", out.columns["a_sum"].at[0].add(1))
        return f

    def dropped(config, devices):
        def f(t):
            out = ops_local.groupby_agg(t, "k", AGGS)
            return Table(out.columns, out.count - 1)
        return f

    def narrowed(config, devices):
        def f(t):
            cols = {c: v.astype(jnp.int32) for c, v in t.columns.items()}
            out = ops_local.groupby_agg(Table(cols, t.count), "k", AGGS)
            return Table({c: v.astype(jnp.int64) for c, v in out.columns.items()}, out.count)
        return f

    return {"altered": altered, "dropped": dropped, "narrowed": narrowed}


@pytest.mark.parametrize("fault", ["altered", "dropped", "narrowed"])
def test_groupby_local_fault_makes_the_run_incorrect(fault, monkeypatch):
    op = importlib.import_module("chipbench.ops.groupby_local")
    monkeypatch.setattr(op, "program", _faults()[fault])
    res = run_tiny(jax.devices()[:1])
    assert not res["correct"] and res["failed"] > 0, res["checks"]
    assert res["checks"]["wrong_rows"]["value"] > 0


def test_cell_lists_its_per_layer_metrics():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in run._metrics_for(manifest["per_layer"], CELL)}
    assert mine == {"key_sort_ms", "segment_reduce_ms", "groupby_roofline"}
    (cell,) = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "closed-1caller")


# ---------------------------------------------------------------------------
# the readers, on a synthetic window and on a recording of the chip
# ---------------------------------------------------------------------------


def test_groupby_roofline_is_join_roofline_over_the_groupby_bytes():
    s = devtrace.Summary((0, 100), 2, {"/device:TPU:0": [("sort.1", "sort", 10, 90, True)]},
                         [("call", 0, 50), ("call", 50, 100)])
    ctx = devtrace.Context(s, "", 10, {"hbm": 819.0, "ici": 0.0},
                           {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9})
    assert _read("groupby_roofline", ctx) == pytest.approx(100 * 1e-9 / (80e-9 / 2))
    assert _read("groupby_roofline", ctx) == _read("join_roofline", ctx)


def test_recorded_chip_trace_reduces_to_the_groupby_stage_metrics():
    # groupby_local at 20,000 rows, 10 calls, on one v5e chip
    s = devtrace.summarize(TESTDATA / "groupby_local_stages.xplane.pb")
    ctx = devtrace.Context(s, (TESTDATA / "groupby_local_stages.hlo.txt").read_text(),
                           20000, {}, {})
    assert (len(s.ops), s.calls) == (1, 10)
    assert _read("key_sort_ms", ctx) > 0 and _read("segment_reduce_ms", ctx) > 0
    for dev, by_stage in stages.stage_ns(s, ctx.hlo).items():
        named = sum(t for st, t in by_stage.items() if st != stages.UNATTRIBUTED)
        assert named >= 0.95 * devtrace.busy_ns(s, dev), (dev, by_stage)
    assert [_read(m, ctx) for m in ("probe_ms", "output_pack_ms", "partition_ms")] == [None] * 3
