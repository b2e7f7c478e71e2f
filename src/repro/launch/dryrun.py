"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this AOT-compiles the real step function (train_step /
prefill / serve_step) against ShapeDtypeStruct inputs on the production mesh
— no allocation — and records:

- ``compiled.memory_analysis()``  (per-device bytes: proves HBM fit)
- ``compiled.cost_analysis()``    (FLOPs / bytes for §Roofline)
- collective wire bytes parsed from the optimized HLO
- the derived roofline terms (launch.hlo_analysis)

Artifacts land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` and
are the single source for EXPERIMENTS.md §Dry-run / §Roofline / §Perf.

Usage:
    python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax

from repro import configs
from repro.launch import hlo_analysis, shapes
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.models.config import ArchConfig
from repro.models.transformer import DistContext
from repro.dist import sharding
from repro.serve.serve_step import make_serve_step
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _mesh_tag(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)


def _ctx_for(cfg: ArchConfig, mesh) -> DistContext:
    dp, tp = sharding.mesh_axes(mesh)
    # MoE: joint ('data','model') expert parallelism (pod stays pure DP)
    ep = tuple(a for a in dp if a != "pod") + (tp,) if cfg.family == "moe" else None
    return DistContext(
        mesh=mesh,
        ep_axis=ep,
        dp_axes=dp,
        tp_axis=tp,
    )


def lower_cell(cfg: ArchConfig, cell: shapes.ShapeCell, mesh, opt_overrides=None,
               microbatches: int | None = None):
    """Build + lower + compile one cell; returns (compiled, lowered, meta)."""
    ctx = _ctx_for(cfg, mesh)
    params_shape = shapes.params_specs(cfg)
    p_specs = sharding.param_specs(cfg, params_shape, mesh)
    p_sh = sharding.shardings_for(mesh, p_specs)
    batch_shape = shapes.input_specs(cfg, cell)
    b_specs = sharding.batch_specs(cfg, batch_shape, mesh)
    b_sh = sharding.shardings_for(mesh, b_specs)

    if cell.kind == "train":
        micro = microbatches or shapes.TRAIN_MICROBATCH.get(cfg.name, cell.microbatches)
        opt_cfg = opt.OptConfig(state_dtype=cfg.opt_state_dtype)
        if opt_overrides:
            opt_cfg = opt_overrides(opt_cfg)
        import jax.numpy as _jnp
        step = make_train_step(
            cfg, opt_cfg, ctx=ctx, microbatches=micro,
            grad_dtype=_jnp.dtype(cfg.param_dtype),
        )
        opt_shape = jax.eval_shape(lambda p: opt.init_state(p, opt_cfg), params_shape)
        o_specs = sharding.param_specs(cfg, opt_shape, mesh)
        o_sh = sharding.shardings_for(mesh, o_specs)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        with mesh:
            lowered = fn.lower(params_shape, opt_shape, batch_shape)
    elif cell.kind == "prefill":
        state_shape = shapes.decode_state_specs(cfg, cell)
        s_specs = sharding.cache_specs(cfg, state_shape, mesh, cell.global_batch)
        s_sh = sharding.shardings_for(mesh, s_specs)

        def prefill_step(params, batch, state):
            logits, st = api.prefill_fn(cfg, params, batch, state, ctx=ctx)
            return logits, st

        fn = jax.jit(
            prefill_step,
            in_shardings=(p_sh, b_sh, s_sh),
            out_shardings=(None, s_sh),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(params_shape, batch_shape, state_shape)
    else:  # decode
        state_shape = shapes.decode_state_specs(cfg, cell)
        s_specs = sharding.cache_specs(cfg, state_shape, mesh, cell.global_batch)
        s_sh = sharding.shardings_for(mesh, s_specs)
        tok_shape = shapes.input_specs(cfg, cell)["tokens"]
        t_specs = sharding.batch_specs(cfg, {"tokens": tok_shape}, mesh)["tokens"]
        t_sh = sharding.shardings_for(mesh, t_specs)
        serve = make_serve_step(cfg, ctx=ctx)
        fn = jax.jit(
            serve,
            in_shardings=(p_sh, t_sh, s_sh),
            out_shardings=(t_sh, s_sh),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(params_shape, tok_shape, state_shape)

    with mesh:
        compiled = lowered.compile()
    return compiled, lowered


def run_cell(
    arch: str, shape_name: str, *, multi_pod: bool = False, save: bool = True,
    variant: str = "baseline", overrides: dict | None = None,
) -> dict:
    import dataclasses as _dc

    cfg = configs.get(arch)
    micro = None
    if overrides:
        overrides = dict(overrides)
        micro = overrides.pop("microbatches", None)
        if overrides:
            cfg = _dc.replace(cfg, **overrides)
    cell = shapes.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    ok, reason = shapes.cell_supported(cfg, cell)
    tag = f"{arch}__{shape_name}__{_mesh_tag(mesh)}"
    if variant != "baseline":
        tag += f"__{variant}"
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": list(mesh.devices.shape),
        "axes": list(mesh.axis_names), "chips": chips, "variant": variant,
    }
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        _save(tag, record, save)
        return record

    t0 = time.time()
    try:
        compiled, lowered = lower_cell(cfg, cell, mesh, microbatches=micro)
    except Exception as e:  # record the failure; dry-run failures are bugs
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        _save(tag, record, save)
        raise
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = hlo_analysis.analyze(hlo, chips)
    mf = hlo_analysis.model_flops(cfg, cell)
    roof = hlo_analysis.Roofline(
        flops_per_device=stats.flops,
        hbm_bytes_per_device=stats.hbm_bytes,
        collective_wire_bytes=stats.collective_wire_bytes,
        model_flops_total=mf,
        chips=chips,
    )
    record.update(
        status="ok",
        compile_s=round(compile_s, 1),
        memory_analysis={
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes_per_device": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            ),
        },
        cost_analysis={k: float(v) for k, v in cost.items() if isinstance(v, int | float)},
        collectives={"counts": stats.collective_counts,
                     "wire_bytes": int(stats.collective_wire_bytes),
                     "by_kind": stats.collective_by_kind},
        roofline=roof.as_dict(),
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
    )
    print(
        f"[dryrun] {tag}: compile {compile_s:.0f}s | "
        f"mem/dev {(record['memory_analysis']['peak_bytes_per_device']) / 2**30:.2f} GiB | "
        f"compute {roof.compute_s*1e3:.2f} ms, memory {roof.memory_s*1e3:.2f} ms, "
        f"collective {roof.collective_s*1e3:.2f} ms -> {roof.dominant}-bound | "
        f"useful {roof.useful_compute_ratio:.2f}"
    )
    print(f"[dryrun] memory_analysis: {mem}")
    _save(tag, record, save)
    return record


def _save(tag: str, record: dict, save: bool):
    if not save:
        return
    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    with open(ARTIFACT_DIR / f"{tag}.json", "w") as f:
        json.dump(record, f, indent=1)


def main():
    # the fake pod: must precede the first device query of the process
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for a in configs.ARCH_IDS:
            for s in shapes.SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape (or --all) required")
        cells.append((args.arch, args.shape))

    failures = []
    for arch, shape_name in cells:
        mesh_tag = "2x16x16" if args.multi_pod else "16x16"
        out = ARTIFACT_DIR / f"{arch}__{shape_name}__{mesh_tag}.json"
        if args.skip_existing and out.exists():
            st = json.loads(out.read_text()).get("status")
            if st in ("ok", "skipped"):
                print(f"[dryrun] skip existing {out.name} ({st})")
                continue
        try:
            run_cell(arch, shape_name, multi_pod=args.multi_pod)
        except Exception as e:
            failures.append((arch, shape_name, str(e)))
            print(f"[dryrun] FAIL {arch} {shape_name}: {e}")
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for f in failures:
            print("   ", f)
        raise SystemExit(1)
    print("[dryrun] all requested cells OK")


if __name__ == "__main__":
    main()
