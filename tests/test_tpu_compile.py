"""Compiles of the main-path programs for a described TPU v5e 2x2 host.

Nothing runs: each program is lowered and compiled by the TPU compiler for
devices that are described, not attached, so a kernel whose tiling the chip
refuses, or a program that does not fit its memory, fails here at no chip
time.  The topology is described inside a fixture, never while a module is
imported, and the tests skip where it cannot be described.
"""

import contextlib
import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from benchmarks import common  # noqa: E402
from benchmarks.scaling_join import WEAK_ROWS  # noqa: E402
from chipbench import groupbyref, run, stages  # noqa: E402
from repro.dataframe import Table, ops_local
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.hash_partition import kernel as hp_kernel
from repro.kernels.join_probe import kernel as jp_kernel
from repro.kernels.segment_reduce import kernel as sr_kernel
from repro.launch.mesh import make_mesh

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip are written to the cache but cannot be
    read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _fits_one_chip(compiled) -> bool:
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    return used - mem.alias_size_in_bytes < V5E_HBM_BYTES


def _kernel_cases():
    keys, page = chip_smoke.KERNEL_KEYS, chip_smoke.PROBE_PAGE
    heads, seq, hd = chip_smoke.ATTN_HEADS, chip_smoke.ATTN_SEQ, chip_smoke.ATTN_HEAD_DIM
    i32 = ((keys,), jnp.int32)
    return {
        "hash_partition": (
            functools.partial(hp_kernel.hash_partition, num_partitions=64), [i32]),
        "join_probe": (jp_kernel.probe_sorted, [((page,), jnp.int32), i32]),
        "segment_reduce": (sr_kernel.segment_sum_blocked, [i32, ((keys,), jnp.float32)]),
        "flash_attention": (
            lambda q, k, v, n: fa_kernel.flash_attention(q, k, v, n, causal=True),
            [((heads, seq, hd), jnp.bfloat16)] * 3 + [((), jnp.int32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_pallas_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_join_unique_compiles_at_weak_scaling_rows(one_chip):
    cap = common.join_capacity(WEAK_ROWS)

    def table(*names):
        col = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one_chip)
        return Table({n: col for n in names},
                     jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))

    compiled = _compile(lambda lt, rt: ops_local.join_unique(lt, rt, "k"),
                        table("k", "v"), table("k", "w"))
    assert _fits_one_chip(compiled)


def test_join_spmd_compiles_on_four_chips_with_all_to_all(topo):
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    sharding = NamedSharding(mesh, P("data"))
    cap = common.join_capacity(WEAK_ROWS)
    col = jax.ShapeDtypeStruct((4 * cap,), jnp.int32, sharding=sharding)
    counts = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=sharding)
    compiled = chip_smoke.dist_join_fn(mesh).lower(
        col, col, counts, col, col, counts).compile()
    assert "all-to-all" in compiled.as_text()
    assert _fits_one_chip(compiled)


def test_groupby_agg_compiles_at_the_groupby_cell_size(one_chip):
    """``groupby-50m-1chip``'s program: three int64 columns of 55,000,008
    slots, a summed and b maxed.  It fits one chip, holds no scatter, and every
    sort, fusion and windowed reduction outside the key sort is the segment
    reduction's: its scans and its compacting sort."""
    _, _, config, _ = run.load_cell("groupby-50m-1chip")
    with jax.enable_x64(True):
        col = jax.ShapeDtypeStruct((groupbyref.capacity(config),), jnp.int64,
                                   sharding=one_chip)
        table = Table({c: col for c in config["columns"]},
                      jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
        compiled = _compile(lambda t: ops_local.groupby_agg(t, config["key"], config["aggs"]),
                            table)
    assert groupbyref.capacity(config) == 55_000_008
    assert _fits_one_chip(compiled)
    hlo = compiled.as_text()
    of = stages.instruction_stages(hlo)
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\(", hlo, re.M)
    assert "scatter" not in {op for _, op in ops}
    work = [(of[name], op) for name, op in ops if op in {"sort", "fusion", "reduce-window"}]
    assert {stage for stage, _ in work} == {"key_sort", "segment_reduce"}
    assert sorted(stage for stage, op in work if op == "sort") == ["key_sort", "segment_reduce"]


SMALL_ROWS = 4096


def _small_tables(one_chip):
    col = jax.ShapeDtypeStruct((SMALL_ROWS,), jnp.int32, sharding=one_chip)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return Table({"k": col, "v": col}, count), Table({"k": col, "w": col}, count)


def _join_unique_text(one_chip) -> str:
    return _compile(lambda lt, rt: ops_local.join_unique(lt, rt, "k"),
                    *_small_tables(one_chip)).as_text()


def test_join_unique_probe_loop_fusions_carry_the_probe_stage(one_chip):
    """The instructions under the probe stage sort, and hold no loop and no gather."""
    hlo = _join_unique_text(one_chip)
    of = stages.instruction_stages(hlo)
    opcodes = {op for name, op in re.findall(r"^\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\(", hlo, re.M)
               if of[name] == "probe"}
    assert "sort" in opcodes
    assert not opcodes & {"while", "gather"}


def _without_metadata(hlo: str) -> str:
    hlo = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n", "", hlo,
                 flags=re.S | re.M)
    return re.sub(r",? (metadata=\{[^}]*\}|stack_frame_id=\d+)", "", hlo)


def test_stage_scopes_change_no_instruction(one_chip, monkeypatch):
    scoped = _join_unique_text(one_chip)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    unscoped = _join_unique_text(one_chip)
    assert "/probe/" in scoped and "/probe/" not in unscoped
    assert _without_metadata(scoped) == _without_metadata(unscoped)
