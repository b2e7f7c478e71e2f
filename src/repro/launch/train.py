"""Training driver: data pipeline -> train loop -> checkpoint/restart.

Library entry used by ``examples/train_pipeline.py`` and runnable directly:

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
        --steps 200
    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --no-reduced \
        --num-layers 4 --batch 2 --seq-len 2048 --steps 5      # one chip

On real hardware the same driver runs under the production mesh (pjit with
the sharding rules); on this host it trains the reduced config on one
device.  Fault tolerance: checkpoint every ``ckpt_every`` steps; restart
resumes from the latest step (tested in test_integration.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs
from repro.data import pipeline
from repro.dist import checkpoint as ckpt
from repro.dist import compression
from repro.dist.object_store import Store, as_store
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step


def build_dataset(cfg, batch: int, seq_len: int, seed: int = 0):
    """Preprocess a synthetic corpus through the dataframe pipeline."""
    (toks, mask), stats = pipeline.preprocess_local(
        *pipeline.synthesize_corpus(
            ndocs=512, doc_len=seq_len, vocab=cfg.vocab_size, seed=seed
        ),
        batch=batch, seq_len=seq_len,
    )
    return (toks, mask), stats


def data_iter(cfg, batch: int, seq_len: int, seed: int = 0, start: int = 0):
    """Infinite size-``batch`` slices, aligned to the *global* step.

    Each synthesized corpus shard is consumed as its ``n`` full batches
    before the next shard is built (one synthesis per ``n`` steps, not one
    per step).  The (shard, slice) cursor is a pure function of the global
    step, so a run resumed at ``start`` fast-forwards through the shard
    sequence and consumes exactly the slices an uninterrupted run would —
    kill/resume loss traces stay identical (test_integration.py).
    """
    step = 0
    shard = 0
    while True:
        (toks, mask), _ = build_dataset(cfg, batch, seq_len, seed=seed + shard)
        n = max(toks.shape[0] // batch, 1)
        for i in range(n):
            if step >= start:
                sl = slice(i * batch, (i + 1) * batch)
                yield {"tokens": toks[sl], "mask": mask[sl].astype(jnp.float32)}
            step += 1
        shard += 1


def train(
    cfg,
    *,
    steps: int = 100,
    batch: int = 4,
    seq_len: int = 64,
    lr: float = 3e-3,
    ckpt_dir: str | Path | Store | None = None,
    ckpt_every: int = 50,
    log_every: int = 10,
    resume: bool = False,
    stop_after: int | None = None,
    comm_session=None,
    burst_at: int | None = None,
    burst_world: int = 0,
    burst_provider: str | None = None,
    shrink_at: int | None = None,
    shrink_world: int = 0,
    recovery_policy: str = "incremental",
    tracer=None,
    log=print,
):
    """Train ``cfg`` for ``steps`` steps.

    ``stop_after`` simulates a bounded worker lifetime (preemption drill):
    the LR schedule stays pinned to ``steps`` but the loop exits after that
    many global steps — a later ``resume=True`` call with the same ``steps``
    continues the identical trajectory from the latest checkpoint.

    ``comm_session`` (a :class:`repro.core.session.CommSession`) models the
    worker's communication fabric: a resumed run is a deadline-killed /
    preempted rank coming back, so it re-bootstraps through the session
    (re-rendezvous + re-punch, priced into the session's event log) before
    training continues — the paper's §V recovery path made explicit.

    ``burst_at``/``burst_world``/``burst_provider`` model a serverful core
    group absorbing a traffic burst: at that global step the session admits
    ``burst_world`` extra workers (optionally from another provider) through
    the incremental ``CommSession.expand`` path — priced against what a cold
    re-bootstrap of the grown world would cost.  The burst only changes the
    priced fabric, never the single-host training math, so kill/resume
    traces stay identical; a run resumed *past* the burst step re-applies
    the expansion to its fresh session so the modeled world matches.

    ``shrink_at``/``shrink_world`` model the inverse event — a fault domain
    evicting the top ``shrink_world`` ranks at that global step.  The
    session prices the detector (suspect -> confirm DETECT events) and then
    shrinks per ``recovery_policy``: ``"incremental"`` (membership
    compaction + relay GC + a survivor barrier, ≪ re-bootstrap) or
    ``"cold"`` (tear down and re-bootstrap the survivor world).  Like
    bursts this only changes the priced fabric — the single-host training
    math and kill/resume traces are untouched, and a run resumed *past* the
    shrink step re-applies it to its fresh session.

    ``tracer`` (a :class:`repro.core.trace.Tracer`) collects the run's full
    modeled timeline on rank 0's lanes: per-step ``compute`` spans (measured
    step time), ``overhead`` spans for data fetch, ``store`` spans for every
    checkpoint op, ``bootstrap`` spans mirrored from the session lifecycle,
    and — when a ``comm_session`` models the worker fabric — one ``comm``
    span per step for the modeled gradient all-reduce over that session's
    world.  Export it with ``Tracer.to_chrome()`` or via
    ``python -m repro.launch.train --trace-out trace.json``.
    """
    opt_cfg = opt.OptConfig(
        lr=lr, warmup_steps=max(steps // 20, 5), total_steps=steps,
        schedule=cfg.schedule, state_dtype=cfg.opt_state_dtype,
    )
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = opt.init_state(params, opt_cfg)

    grad_comm = None
    grad_nbytes = 0
    if tracer is not None:
        if comm_session is not None:
            # live mirroring: rebootstrap/expand events land as rank-0
            # bootstrap spans the moment the session prices them
            comm_session.attach_tracer(tracer, ranks=(0,))
            from repro.core.communicator import Communicator

            grad_comm = Communicator(session=comm_session)
            grad_nbytes = int(sum(
                x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(params)
            ))
            if cfg.grad_compression:
                grad_nbytes = int(
                    compression.wire_bytes_saved(params)["compressed_bytes"])
        if ckpt_dir is not None:
            # wrap once so every checkpoint op mirrors onto the store lane
            ckpt_dir = as_store(ckpt_dir)
            ckpt_dir.attach_tracer(tracer)

    # Explicit compressed dp-reduction (ROADMAP item): when the flag is set
    # and >1 local device is available, replace XLA's implicit all-reduce
    # with the shard_map int8+error-feedback reduction.  Its error-feedback
    # residual is training state: it joins the checkpoint tree so kill/resume
    # reproduces the uninterrupted trajectory (a run must resume in the same
    # mode it was saved in).
    dp = jax.device_count()
    use_explicit_dp = cfg.grad_compression and dp > 1 and batch % dp == 0
    grad_err = None
    if use_explicit_dp:
        from repro.train.train_step import make_compressed_dp_train_step

        mesh = make_mesh((dp,), ("data",))
        step_fn, init_err = make_compressed_dp_train_step(cfg, opt_cfg, mesh)
        grad_err = init_err(params)
    else:
        step_fn = jax.jit(make_train_step(cfg, opt_cfg))
    log(f"train step: {'explicit compressed-dp shard_map' if use_explicit_dp else 'implicit-dp jit'} "
        f"over {dp} device(s)")

    def ckpt_tree():
        tree = {"params": params, "opt": opt_state}
        if use_explicit_dp:
            tree["grad_err"] = grad_err
        return tree

    start = 0
    if resume and ckpt_dir and (latest := ckpt.latest(ckpt_dir)):
        tree = ckpt.restore(latest, ckpt_tree())
        params, opt_state = tree["params"], tree["opt"]
        if use_explicit_dp:
            grad_err = tree["grad_err"]
        start = ckpt.read_manifest(latest)["step"]
        log(f"resumed from step {start}")
        if comm_session is not None and start > 0:
            reboot_s = comm_session.rebootstrap_rank(0)
            log(f"re-bootstrap: rank 0 re-joined its CommSession "
                f"(world {comm_session.world}) in {reboot_s:.1f}s modeled "
                f"rendezvous + re-punch")

    if cfg.grad_compression:
        rep = compression.wire_bytes_saved(params)
        log(f"grad compression: int8+scales {rep['compressed_bytes']/2**20:.1f} MiB "
            f"vs bf16 {rep['bf16_bytes']/2**20:.1f} MiB "
            f"({rep['ratio_vs_bf16']:.2f}x) per exchange")
        # tuned-engine dp-reduction model (vs the implicit f32 all-reduce the
        # XLA path would issue), Lambda-direct at the paper's 64-node point
        from repro.core import algorithms, netsim

        implicit = algorithms.select_algorithm(
            "allreduce", 64, 4 * rep["elements"], netsim.LAMBDA_DIRECT)
        explicit = algorithms.select_algorithm(
            "allgather", 64, rep["compressed_bytes"], netsim.LAMBDA_DIRECT)
        why_off = (
            "" if use_explicit_dp
            else " (single device)" if dp == 1
            else f" (batch {batch} not divisible by {dp} devices)"
        )
        log(f"dp-reduction model @64/lambda-direct: implicit f32 all-reduce "
            f"{implicit.time_s*1e3:.1f} ms ({implicit.algorithm}) vs explicit "
            f"int8 allgather {explicit.time_s*1e3:.1f} ms ({explicit.algorithm}); "
            f"explicit path {'ON' if use_explicit_dp else 'off' + why_off}")

    def apply_burst():
        nonlocal grad_comm
        expand_s = comm_session.expand(burst_world, provider=burst_provider)
        if grad_comm is not None:
            from repro.core.communicator import Communicator

            grad_comm = Communicator(session=comm_session)
        full_s = comm_session.full_rebootstrap_time_s()
        who = f" from {burst_provider}" if burst_provider else ""
        log(f"burst: +{burst_world} workers{who} admitted at step {burst_at} "
            f"-> world {comm_session.world}; incremental expand {expand_s:.1f}s "
            f"modeled vs {full_s:.1f}s cold re-bootstrap of the grown world "
            f"({expand_s / max(full_s, 1e-9):.0%})")

    def apply_shrink():
        nonlocal grad_comm
        dead = list(range(comm_session.world - shrink_world,
                          comm_session.world))
        label = "_".join(f"r{r}" for r in dead)
        detect_s = comm_session.detect_failure(label)
        shrink_s = comm_session.shrink(dead, policy=recovery_policy)
        if grad_comm is not None:
            from repro.core.communicator import Communicator

            grad_comm = Communicator(session=comm_session)
        # baseline: what a cold re-bootstrap of the survivor world costs
        full_s = comm_session.full_rebootstrap_time_s()
        log(f"shrink: ranks {dead} evicted at step {shrink_at} -> world "
            f"{comm_session.world}; detect {detect_s:.1f}s + "
            f"{recovery_policy} shrink {shrink_s:.1f}s modeled vs "
            f"{full_s:.1f}s cold re-bootstrap of the survivor world "
            f"({(detect_s + shrink_s) / max(full_s, 1e-9):.0%})")

    do_burst = (
        comm_session is not None and burst_at is not None and burst_world > 0
    )
    if do_burst and start > burst_at:
        # resumed past the burst: the expanded world is part of history
        apply_burst()
        do_burst = False
    do_shrink = (
        comm_session is not None and shrink_at is not None and shrink_world > 0
    )
    if do_shrink and start > shrink_at:
        # resumed past the eviction: the shrunk world is part of history
        apply_shrink()
        do_shrink = False

    # start the iterator at the global step so a resumed run consumes the
    # same data slices an uninterrupted run would (loss-trace continuity)
    it = data_iter(cfg, batch, seq_len, start=start)
    losses = []
    t0 = time.time()
    end = steps if stop_after is None else min(steps, stop_after)
    for step in range(start, end):
        if do_burst and step == burst_at:
            apply_burst()
            do_burst = False
        if do_shrink and step == shrink_at:
            apply_shrink()
            do_shrink = False
        t_fetch = time.perf_counter()
        batch_data = next(it)
        fetch_s = time.perf_counter() - t_fetch
        t_step = time.perf_counter()
        if use_explicit_dp:
            params, opt_state, grad_err, metrics = step_fn(
                params, opt_state, grad_err, batch_data)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch_data)
        losses.append(float(metrics["loss"]))
        if tracer is not None:
            tracer.span(0, "overhead", "data_fetch",
                        duration_s=fetch_s, step=step)
            tracer.span(0, "compute", "train_step",
                        duration_s=time.perf_counter() - t_step, step=step)
            if grad_comm is not None:
                tracer.span(
                    0, "comm", "grad_allreduce",
                    duration_s=grad_comm.collective_time_s(
                        "allreduce", grad_nbytes),
                    nbytes=grad_nbytes, step=step,
                    world=comm_session.world,
                )
        # `end - 1`, not `steps - 1`: a --stop-after preemption drill must
        # still log the last step it actually executed
        if step % log_every == 0 or step == end - 1:
            log(f"step {step:4d} loss {losses[-1]:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"({(time.time()-t0)/max(step-start+1,1):.2f}s/step)")
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt.save(ckpt_dir, step + 1, ckpt_tree())
    # checkpoint on the way out (graceful preemption / end of run) so a
    # stop_after drill never exits with unsaved progress
    if ckpt_dir and end > start and end % ckpt_every != 0:
        ckpt.save(ckpt_dir, end, ckpt_tree())
    if tracer is not None and tracer.spans:
        lanes = ", ".join(
            f"{lane} {tracer.lane_time_s(lane):.3f}s"
            for lane in ("compute", "comm", "store", "bootstrap", "overhead")
            if tracer.lane_time_s(lane) > 0.0
        )
        log(f"trace: {len(tracer.spans)} spans — {lanes}")
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction, default=True,
                    help="shrink to the tiny CPU smoke widths (--no-reduced keeps "
                         "the published widths)")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="cut the config's depth to this many layers")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--stop-after", type=int, default=None,
                    help="exit after this many global steps (preemption drill)")
    ap.add_argument("--comm-world", type=int, default=32,
                    help="modeled communication-session world for the "
                         "re-bootstrap pricing on --resume")
    ap.add_argument("--comm-fabric", default="lambda",
                    help="fabric or registered provider name for the modeled "
                         "communication session (e.g. lambda, aws-ec2)")
    ap.add_argument("--burst-at", type=int, default=None,
                    help="global step at which the modeled session absorbs a "
                         "traffic burst (requires --burst-world)")
    ap.add_argument("--burst-world", type=int, default=0,
                    help="workers admitted at --burst-at via the incremental "
                         "expand path")
    ap.add_argument("--burst-provider", default=None,
                    help="provider the burst workers come from (cross-provider "
                         "pairs relay; default: the core fabric's)")
    ap.add_argument("--shrink-at", type=int, default=None,
                    help="global step at which a fault domain evicts workers "
                         "from the modeled session (requires --shrink-world)")
    ap.add_argument("--shrink-world", type=int, default=0,
                    help="workers evicted at --shrink-at (the top ranks)")
    ap.add_argument("--recovery-policy", default="incremental",
                    choices=("incremental", "cold"),
                    help="how the session recovers from the eviction: "
                         "incremental shrink (membership compaction + relay "
                         "GC) or a cold re-bootstrap of the survivors")
    ap.add_argument("--trace-out", default=None,
                    help="write the run's modeled span timeline here as raw "
                         "JSON (convert with scripts/trace_to_chrome.py for "
                         "chrome://tracing)")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=args.num_layers)
    comm_session = None
    # --trace-out wants comm spans too, so it also builds the modeled session
    if args.resume or (args.burst_at is not None and args.burst_world > 0) \
            or (args.shrink_at is not None and args.shrink_world > 0) \
            or args.trace_out is not None:
        from repro.core.session import CommSession

        comm_session = CommSession.bootstrap(args.comm_world, args.comm_fabric)
    tracer = None
    if args.trace_out is not None:
        from repro.core.trace import Tracer

        tracer = Tracer()
    _, losses = train(
        cfg, steps=args.steps, batch=args.batch, seq_len=args.seq_len,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, stop_after=args.stop_after,
        comm_session=comm_session,
        burst_at=args.burst_at, burst_world=args.burst_world,
        burst_provider=args.burst_provider,
        shrink_at=args.shrink_at, shrink_world=args.shrink_world,
        recovery_policy=args.recovery_policy,
        tracer=tracer,
    )
    if tracer is not None:
        import json

        Path(args.trace_out).write_text(json.dumps(tracer.to_json()))
        cp = tracer.critical_path()
        lanes = ", ".join(f"{k} {v:.3f}s" for k, v in cp["lanes"].items())
        print(f"trace written to {args.trace_out}: {len(tracer.spans)} spans; "
              f"critical rank {cp['rank']} chain {cp['total_s']:.3f}s ({lanes})")
    if losses:
        print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")
    else:
        print("no steps to run (already at or past the target step)")


if __name__ == "__main__":
    main()
