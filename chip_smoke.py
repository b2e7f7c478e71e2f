"""Bring-up smoke run of the system's main paths on a TPU.

    python chip_smoke.py              # one chip: join, groupby, train, serve, kernels
    python chip_smoke.py --chips 4    # four chips: join_spmd + groupby_spmd only

One process drives everything through the library's own entry points at
real sizes: the paper's join (9.1M rows per worker) and groupby (50M rows
over 1000 keys), minicpm-2b at its published widths cut in depth only
(``launch.train.train``, then prefill + decode through ``serve_step``), and
the four Pallas kernels compiled for the chip.  Each phase checks its output
against an independent reference and prints one line: its sizes, its wall
time with compilation included, the device's ``peak_bytes_in_use`` so far
and the check.  These are bring-up numbers, not a benchmark.

The last line is ``{"ok": true, "device": {...}}`` naming the device as JAX
reports it.  Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO / "src"), str(REPO)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks import common  # noqa: E402
from benchmarks.groupby_scaling import NGROUPS, ROWS_PER_NODE  # noqa: E402
from benchmarks.scaling_join import WEAK_ROWS  # noqa: E402
from repro import configs  # noqa: E402
from repro.dataframe import Table, ops_dist, ops_local  # noqa: E402
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref  # noqa: E402
from repro.kernels.hash_partition import ops as hp_ops, ref as hp_ref  # noqa: E402
from repro.kernels.join_probe import ops as jp_ops, ref as jp_ref  # noqa: E402
from repro.kernels.segment_reduce import ops as sr_ops, ref as sr_ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import api  # noqa: E402
from repro.serve.serve_step import make_prefill_step, make_serve_step  # noqa: E402

ARCH = "minicpm-2b"
TRAIN_LAYERS = 4          # of 40; the depth that fits one v5e chip with Adam
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 6
SERVE_BATCH, SERVE_PROMPT, SERVE_NEW = 8, 512, 16
DECODE_ATOL = DECODE_RTOL = 5e-2   # bf16 logits: decode vs teacher-forced
KERNEL_KEYS, PROBE_PAGE = 1 << 22, 32768
ATTN_HEADS, ATTN_SEQ, ATTN_HEAD_DIM = 36, 4096, 64


# ---------------------------------------------------------------------------
# data and references
# ---------------------------------------------------------------------------


def groupby_columns(rows: int, ngroups: int, seed: int) -> dict[str, np.ndarray]:
    """The paper's groupby table: keys over `ngroups`, two int value columns."""
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, ngroups, rows).astype(np.int32),
            "v": rng.integers(0, 100, rows).astype(np.int32),
            "w": rng.integers(0, 100, rows).astype(np.int32)}


def join_reference(left: dict, right: dict) -> tuple[np.ndarray, ...]:
    k, li, ri = np.intersect1d(left["k"], right["k"], assume_unique=True,
                               return_indices=True)
    return k, left["v"][li], right["w"][ri]


def groupby_reference(cols: dict, ngroups: int) -> tuple[np.ndarray, ...]:
    counts = np.bincount(cols["k"], minlength=ngroups)
    sums = np.bincount(cols["k"], weights=cols["v"], minlength=ngroups)
    keys = np.nonzero(counts)[0]
    return keys, sums[keys].astype(np.int64), counts[keys]


def _compare(name: str, got: tuple, want: tuple) -> list[str]:
    """Exact comparison of column tuples; got rows may come in any order."""
    order = np.argsort(got[0], kind="stable")
    got = tuple(np.asarray(g)[order] for g in got)
    if len(got[0]) != len(want[0]):
        return [f"{name}: {len(got[0])} rows, reference {len(want[0])}"]
    return [f"{name}: column {i} differs" for i, (g, w) in enumerate(zip(got, want))
            if not np.array_equal(g.astype(np.int64), np.asarray(w).astype(np.int64))]


# ---------------------------------------------------------------------------
# one-chip phases: each returns (sizes, failures)
# ---------------------------------------------------------------------------


def phase_join(rows: int, seed: int) -> tuple[str, list[str]]:
    left, right = common.gen_join_tables(rows, seed)
    out = jax.jit(lambda lt, rt: ops_local.join_unique(lt, rt, "k"))(left, right)
    got = out.to_numpy()
    want = join_reference(left.to_numpy(), right.to_numpy())
    return (f"rows={rows} per table, matches={len(got['k'])}",
            _compare("join", (got["k"], got["v"], got["w"]), want))


def phase_groupby(rows: int, ngroups: int, seed: int) -> tuple[str, list[str]]:
    cols = groupby_columns(rows, ngroups, seed)
    out = jax.jit(lambda t: ops_local.groupby_agg(t, "k", {"v": "sum", "w": "count"}))(
        Table.from_dict(cols))
    got = out.to_numpy()
    want = groupby_reference(cols, ngroups)
    return (f"rows={rows} keys={ngroups} groups={len(got['k'])}",
            _compare("groupby", (got["k"], got["v_sum"], got["w_count"]), want))


def phase_train(cfg, steps: int, batch: int, seq_len: int):
    """Returns (sizes, failures, trained params)."""
    lines: list[str] = []
    params, losses = train(cfg, steps=steps, batch=batch, seq_len=seq_len,
                           log=lines.append)
    step_fn = next(line for line in lines if line.startswith("train step:"))
    failures = []
    if not all(np.isfinite(losses)):
        failures.append(f"non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        failures.append(f"loss did not fall: {losses}")
    sizes = (f"{cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
             f"heads={cfg.num_heads}x{cfg.resolved_head_dim} d_ff={cfg.d_ff} "
             f"vocab={cfg.vocab_size} batch={batch} seq={seq_len} steps={steps} "
             f"[{step_fn}] losses={[float(x) for x in losses]}")
    return sizes, failures, params


def phase_serve(cfg, params, batch: int, prompt: int, new: int, seed: int):
    """Prefill + `new` decode steps; every step's logits vs teacher forcing."""
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(1, cfg.vocab_size, (batch, prompt)), jnp.int32)
    state = api.init_decode_state(cfg, batch, prompt + new)
    prefill = jax.jit(make_prefill_step(cfg, with_logits=True))
    step = jax.jit(make_serve_step(cfg, with_logits=True), donate_argnums=(2,))
    tok, logits, state = prefill(params, {"tokens": prompts}, state)
    fed, step_logits = [], [logits[:, 0]]
    for _ in range(new):
        fed.append(tok)
        tok, logits, state = step(params, tok, state)
        step_logits.append(logits[:, 0])
    seq = jnp.concatenate([prompts] + fed, axis=1)
    teacher = jax.jit(
        lambda p, t: api.logits_fn(cfg, p, {"tokens": t})[0][:, prompt - 1:])(params, seq)
    got = np.stack([np.asarray(x, np.float32) for x in step_logits], axis=1)
    want = np.asarray(teacher, np.float32)
    err = np.abs(got - want)
    bad = err > DECODE_ATOL + DECODE_RTOL * np.abs(want)
    failures = [] if np.isfinite(got).all() and not bad.any() else [
        f"decode logits off teacher forcing at {int(bad.sum())} of {bad.size} "
        f"entries (max abs err {float(err.max())!r})"]
    sizes = (f"batch={batch} prompt={prompt} decoded={new} "
             f"max_abs_logit_err={float(err.max())!r} tol={DECODE_ATOL}+{DECODE_RTOL}*|ref|")
    return sizes, failures


def _run_kernel(fn, *args):
    """Compile `fn`, run it; also report whether a Mosaic kernel is in it."""
    lowered = jax.jit(fn).lower(*args)
    return lowered.compile()(*args), "tpu_custom_call" in lowered.as_text()


def phase_kernels(keys: int, page: int, segments: int, heads: int, seq: int,
                  head_dim: int, seed: int) -> tuple[str, list[str]]:
    """Each Pallas kernel once at `keys` keys / a `page`-key probe page /
    `segments` sorted segments / `heads` x `seq` x `head_dim` attention,
    against its ref.py."""
    rng = np.random.default_rng(seed)
    failures, mosaic = [], {}

    k = jnp.asarray(rng.integers(-(2**31), 2**31 - 1, keys), jnp.int32)
    (h, b), mosaic["hash_partition"] = _run_kernel(
        lambda x: hp_ops.hash_partition(x, num_partitions=64, force_kernel=True), k)
    h_r, b_r = hp_ref.hash_partition_ref(k, num_partitions=64)
    if not (np.array_equal(np.asarray(h), np.asarray(h_r))
            and np.array_equal(np.asarray(b), np.asarray(b_r))):
        failures.append("hash_partition differs from ref")

    right = np.unique(rng.integers(0, 10 * page, page)).astype(np.int32)
    right = np.concatenate([right, np.full(page - len(right), np.iinfo(np.int32).max, np.int32)])
    rk = jnp.asarray(right)
    lk = jnp.asarray(rng.integers(0, 10 * page, keys), jnp.int32)
    (idx, hit), mosaic["join_probe"] = _run_kernel(
        lambda r, l: jp_ops.probe_sorted(r, l, force_kernel=True), rk, lk)
    idx_r, hit_r = jp_ref.probe_sorted_ref(rk, lk)
    hit, hit_r = np.asarray(hit), np.asarray(hit_r)
    if not (np.array_equal(hit, hit_r)
            and np.array_equal(np.asarray(idx)[hit], np.asarray(idx_r)[hit])):
        failures.append("join_probe differs from ref")

    seg = jnp.asarray(np.sort(rng.integers(0, segments, keys)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=keys).astype(np.float32))
    sums, mosaic["segment_reduce"] = _run_kernel(
        lambda s, v: sr_ops.segment_sum(s, v, segments, force_kernel=True), seg, vals)
    if not np.allclose(np.asarray(sums),
                       np.asarray(sr_ref.segment_sum_ref(seg, vals, segments)),
                       atol=1e-2, rtol=1e-4):
        failures.append("segment_reduce differs from ref")

    q, kk, v = (jnp.asarray(rng.normal(size=(1, seq, heads, head_dim)), jnp.bfloat16)
                for _ in range(3))
    out, mosaic["flash_attention"] = _run_kernel(
        lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True, force_kernel=True),
        q, kk, v)
    head_major = [x.transpose(0, 2, 1, 3).reshape(heads, seq, head_dim) for x in (q, kk, v)]
    want = fa_ref.attention_ref(*head_major, seq, causal=True)
    got = out.transpose(0, 2, 1, 3).reshape(heads, seq, head_dim)
    if not np.allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                       atol=2e-2, rtol=2e-2):
        failures.append("flash_attention differs from ref")

    if jax.default_backend() == "tpu":
        failures += [f"{name} ran without a Mosaic kernel" for name, m in mosaic.items() if not m]
    sizes = (f"hash/probe/segment keys={keys} probe_page={page} segments={segments} "
             f"attention={heads}x{seq}x{head_dim} bf16 mosaic={mosaic}")
    return sizes, failures


# ---------------------------------------------------------------------------
# four-chip phase: the distributed dataframe over an all_to_all shuffle
# ---------------------------------------------------------------------------


def dist_join_fn(mesh):
    def body(lk, lv, lc, rk, rw, rc):
        out = ops_dist.join_spmd(Table({"k": lk, "v": lv}, lc[0]),
                                 Table({"k": rk, "w": rw}, rc[0]), "k", "data")
        return out.columns["k"], out.columns["v"], out.columns["w"], out.count.reshape(1)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),) * 6,
                                 out_specs=(P("data"),) * 4))


def dist_groupby_fn(mesh):
    def body(k, v, w, c):
        out = ops_dist.groupby_spmd(Table({"k": k, "v": v, "w": w}, c[0]), "k",
                                    {"v": "sum", "w": "count"}, "data")
        return (out.columns["k"], out.columns["v_sum"], out.columns["w_count"],
                out.count.reshape(1))

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),) * 4,
                                 out_specs=(P("data"),) * 4))


def _shard_columns(cols: dict, shards: int, cap: int, sharding) -> tuple[list, jax.Array]:
    """Split each column's rows evenly over `shards`, pad each to `cap`."""
    rows = len(next(iter(cols.values()))) // shards
    out = []
    for x in cols.values():
        buf = np.zeros((shards, cap), x.dtype)
        buf[:, :rows] = x.reshape(shards, rows)
        out.append(jax.device_put(buf.reshape(-1), sharding))
    return out, jax.device_put(np.full(shards, rows, np.int32), sharding)


def _gather_shards(arrays, counts) -> tuple[np.ndarray, ...]:
    counts = np.asarray(counts)
    cols = [np.asarray(a).reshape(len(counts), -1) for a in arrays]
    return tuple(np.concatenate([c[s, :n] for s, n in enumerate(counts)]) for c in cols)


def phase_distributed(devices, join_rows: int, groupby_rows: int, ngroups: int,
                      seed: int) -> tuple[str, list[str]]:
    """join_spmd and groupby_spmd on a 1-D mesh over `devices`, per-device
    sizes, against NumPy over the union of the shards."""
    n = len(devices)
    mesh = make_mesh((n,), ("data",), devices=devices)
    sharding = NamedSharding(mesh, P("data"))

    left, right = common.join_columns(n * join_rows, seed)
    cap = common.join_capacity(join_rows)
    lcols, lc = _shard_columns(left, n, cap, sharding)
    rcols, rc = _shard_columns(right, n, cap, sharding)
    *cols, counts = dist_join_fn(mesh)(*lcols, lc, *rcols, rc)
    got = _gather_shards(cols, counts)
    failures = _compare("join_spmd", got, join_reference(left, right))
    matches = len(got[0])

    gcols = groupby_columns(n * groupby_rows, ngroups, seed + 1)
    gc, gcount = _shard_columns(gcols, n, groupby_rows, sharding)
    *cols, counts = dist_groupby_fn(mesh)(*gc, gcount)
    got = _gather_shards(cols, counts)
    failures += _compare("groupby_spmd", got, groupby_reference(gcols, ngroups))
    sizes = (f"mesh=({n},) join rows={join_rows} per chip per table matches={matches}; "
             f"groupby rows={groupby_rows} per chip keys={ngroups} groups={len(got[0])}")
    return sizes, failures


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max(peaks) if None not in peaks else None


def _report(name: str, devices, t0: float, sizes: str, failures: list[str]) -> None:
    check = "pass" if not failures else "FAIL: " + "; ".join(failures)
    print(f"phase {name}: {sizes} | wall_s_incl_compile={time.perf_counter() - t0!r} "
          f"| peak_bytes_in_use={_peak_bytes(devices)} | check={check}", flush=True)
    if failures:
        raise SystemExit(1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed join and groupby on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); nothing run",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {count} device(s)",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    devices = jax.devices()[:args.chips]
    print(f"device: kind={dev.device_kind!r} count={count} using={len(devices)} "
          f"compile_cache={cache}", flush=True)

    if args.chips == 4:
        t0 = time.perf_counter()
        _report("distributed", devices, t0,
                *phase_distributed(devices, WEAK_ROWS, ROWS_PER_NODE, NGROUPS, args.seed))
    else:
        t0 = time.perf_counter()
        _report("join", devices, t0, *phase_join(WEAK_ROWS, args.seed))
        t0 = time.perf_counter()
        _report("groupby", devices, t0, *phase_groupby(ROWS_PER_NODE, NGROUPS, args.seed))
        cfg = dataclasses.replace(configs.get(ARCH), num_layers=TRAIN_LAYERS)
        print(f"cut: {ARCH} layers {TRAIN_LAYERS} of {configs.get(ARCH).num_layers}, "
              f"train batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, serve batch {SERVE_BATCH} "
              f"x prompt {SERVE_PROMPT} + {SERVE_NEW} decoded; widths as published",
              flush=True)
        t0 = time.perf_counter()
        sizes, failures, params = phase_train(cfg, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ)
        _report("train", devices, t0, sizes, failures)
        t0 = time.perf_counter()
        _report("serve", devices, t0,
                *phase_serve(cfg, params, SERVE_BATCH, SERVE_PROMPT, SERVE_NEW, args.seed))
        del params
        t0 = time.perf_counter()
        _report("kernels", devices, t0,
                *phase_kernels(KERNEL_KEYS, PROBE_PAGE, NGROUPS, ATTN_HEADS, ATTN_SEQ,
                               ATTN_HEAD_DIM, args.seed))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
