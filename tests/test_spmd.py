"""Multi-device SPMD integration tests.

These need >1 XLA device, so each runs in a subprocess with
``--xla_force_host_platform_device_count=8`` (the main pytest process keeps
the default single device per the dry-run isolation rule).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path


REPO = Path(__file__).resolve().parents[1]


def run_spmd(body: str, timeout=900) -> str:
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    return res.stdout


class TestDataframeSPMD:
    def test_join_and_groupby_under_shard_map(self):
        run_spmd(
            """
            from repro.dataframe import Table, ops_dist
            P_ = 8
            mesh = make_mesh((P_,), ("data",))
            rng = np.random.default_rng(1)
            n_per = 64
            keys = rng.permutation(P_*n_per).astype(np.int32)
            vals = rng.integers(0, 100, P_*n_per).astype(np.int32)
            rkeys = rng.permutation(P_*n_per).astype(np.int32)[:P_*n_per//2]
            rvals = rng.integers(0, 9, P_*n_per//2).astype(np.int32)

            def sharded_cols(k, v, names, cap):
                per = len(k)//P_
                kc = np.zeros((P_, cap), np.int32); vc = np.zeros((P_, cap), np.int32)
                for s_ in range(P_):
                    kc[s_, :per] = k[s_*per:(s_+1)*per]; vc[s_, :per] = v[s_*per:(s_+1)*per]
                return ({names[0]: jnp.asarray(kc.reshape(-1)), names[1]: jnp.asarray(vc.reshape(-1))},
                        jnp.asarray(np.full(P_, per, np.int32)))

            lcols, lcounts = sharded_cols(keys, vals, ('k','v'), n_per)
            rcols, rcounts = sharded_cols(rkeys, rvals, ('k','w'), n_per)

            def body(lk, lv, lc, rk, rv, rc):
                lt = Table({'k': lk, 'v': lv}, lc[0])
                rt = Table({'k': rk, 'w': rv}, rc[0])
                out = ops_dist.join_spmd(lt, rt, 'k', 'data')
                return out.columns['k'], out.columns['v'], out.columns['w'], out.count.reshape(1)

            f = jax.shard_map(body, mesh=mesh,
                in_specs=(P('data'),)*6, out_specs=(P('data'),)*4)
            jk, jv, jw, jcnt = map(np.asarray, jax.jit(f)(
                lcols['k'], lcols['v'], lcounts, rcols['k'], rcols['w'], rcounts))
            got = []
            cap = jk.shape[0]//P_
            for s in range(P_):
                c = jcnt[s]
                got += list(zip(jk[s*cap:s*cap+c].tolist(), jv[s*cap:s*cap+c].tolist(), jw[s*cap:s*cap+c].tolist()))
            rmap = dict(zip(rkeys.tolist(), rvals.tolist()))
            exp = sorted((int(k), int(v), rmap[int(k)]) for k, v in zip(keys, vals) if int(k) in rmap)
            assert sorted(got) == exp, (len(got), len(exp))
            print("JOIN_OK", len(got))
            """
        )

    def test_compressed_shuffle_under_shard_map(self):
        """compress=True: keys bit-exact across the alltoall, float values
        within one block-int8 quantization step of the uncompressed path."""
        run_spmd(
            """
            from repro.dataframe import Table, ops_dist
            P_ = 8
            mesh = make_mesh((P_,), ("data",))
            rng = np.random.default_rng(4)
            n_per = 64; cap = n_per * 2
            keys = rng.permutation(P_*n_per).astype(np.int32)
            vals = (rng.normal(size=P_*n_per) * 10).astype(np.float32)
            kc = np.zeros((P_, cap), np.int32); vc = np.zeros((P_, cap), np.float32)
            for s_ in range(P_):
                kc[s_, :n_per] = keys[s_*n_per:(s_+1)*n_per]
                vc[s_, :n_per] = vals[s_*n_per:(s_+1)*n_per]
            counts = jnp.asarray(np.full(P_, n_per, np.int32))

            def body(compress):
                def f(k, v, c):
                    t = Table({'k': k, 'v': v}, c[0])
                    out = ops_dist.shuffle_spmd(t, 'k', 'data', compress=compress)
                    return out.columns['k'], out.columns['v'], out.count.reshape(1)
                return f

            outs = {}
            for compress in (False, True):
                f = jax.shard_map(body(compress), mesh=mesh,
                    in_specs=(P('data'),)*3, out_specs=(P('data'),)*3)
                K, V, C = map(np.asarray, jax.jit(f)(
                    jnp.asarray(kc.reshape(-1)), jnp.asarray(vc.reshape(-1)), counts))
                K = K.reshape(P_, -1); V = V.reshape(P_, -1)
                gk = np.concatenate([K[s][:C[s]] for s in range(P_)])
                gv = np.concatenate([V[s][:C[s]] for s in range(P_)])
                outs[compress] = (gk, gv)
            assert np.array_equal(np.sort(outs[True][0]), np.sort(keys))
            assert np.array_equal(outs[False][0], outs[True][0])  # identical routing
            err = np.abs(outs[False][1] - outs[True][1]).max()
            bound = np.abs(vals).max() / 254 * 1.01 + 1e-6
            assert err <= bound, (err, bound)
            print("COMPRESSED_SHUFFLE_OK", float(err))
            """
        )


class TestCollectiveLowerings:
    def test_allreduce_decomposed_matches_psum(self):
        """Rabenseifner lowering (reduce_scatter + all_gather) == psum/pmean,
        including shapes that don't divide the axis (padded)."""
        run_spmd(
            """
            from repro.core.backends import direct
            mesh = make_mesh((8,), ("data",))
            rng = np.random.default_rng(2)
            for shape in ((64,), (3, 5), (13,)):
                x_all = jnp.asarray(rng.normal(size=(8,) + shape), jnp.float32)

                def body(x):
                    x = x[0]
                    return (direct.allreduce_decomposed(x, "data")[None],
                            direct.allreduce_decomposed(x, "data", mean=True)[None],
                            jax.lax.psum(x, "data")[None])

                f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P("data"),), out_specs=(P("data"),)*3))
                dec, dec_mean, ps = map(np.asarray, f(x_all))
                np.testing.assert_allclose(dec[0], ps[0], rtol=1e-6, atol=1e-6)
                np.testing.assert_allclose(dec_mean[0], ps[0] / 8, rtol=1e-6, atol=1e-6)
                np.testing.assert_allclose(dec, np.broadcast_to(dec[:1], dec.shape))
            print("DECOMPOSED_OK")
            """
        )

    def test_staged_chunked_matches_monolithic(self):
        """Chunked pipelined staging moves identical data to the monolithic
        PUT/GET hop (the time difference lives in the cost engine)."""
        run_spmd(
            """
            from repro.core.backends import mediated
            mesh = make_mesh((8,), ("data",))
            rng = np.random.default_rng(3)
            x_all = jnp.asarray(rng.normal(size=(8, 8, 16, 4)), jnp.float32)

            def body(chunks):
                def f(x):
                    x = x[0]
                    mono = mediated.staged_all_to_all(x, "data")
                    chk = mediated.staged_all_to_all_chunked(x, "data", chunks=chunks)
                    return mono[None], chk[None]
                return f

            for chunks in (2, 4):
                f = jax.jit(jax.shard_map(body(chunks), mesh=mesh,
                    in_specs=(P("data"),), out_specs=(P("data"),)*2))
                mono, chk = map(np.asarray, f(x_all))
                np.testing.assert_array_equal(mono, chk)
            print("CHUNKED_OK")
            """
        )


class TestCompressedDPStep:
    def test_explicit_reduction_tracks_implicit(self):
        """make_compressed_dp_train_step (explicit shard_map int8 dp-reduction)
        stays within quantization error of the implicit-XLA-all-reduce step:
        identical loss at step 0, close params after three updates."""
        run_spmd(
            """
            import dataclasses
            from repro import configs
            from repro.models import api
            from repro.train import optimizer as opt
            from repro.train.train_step import (
                make_compressed_dp_train_step, make_train_step)

            cfg = configs.get('gemma3-4b').reduced(
                vocab_size=512, d_model=128, num_heads=4, head_dim=32,
                num_kv_heads=2)
            cfg = dataclasses.replace(cfg, grad_compression=True)
            opt_cfg = opt.OptConfig(lr=1e-2, warmup_steps=2, total_steps=8,
                schedule=cfg.schedule, state_dtype=cfg.opt_state_dtype)
            params = api.init_params(cfg, jax.random.PRNGKey(0))
            opt_state = opt.init_state(params, opt_cfg)
            rng = np.random.default_rng(0)
            batch = {"tokens": jnp.asarray(rng.integers(0, 512, (8, 16)), jnp.int32),
                     "mask": jnp.ones((8, 16), jnp.float32)}

            mesh = make_mesh((8,), ("data",))
            step_c, init_err = make_compressed_dp_train_step(cfg, opt_cfg, mesh)
            err = init_err(params)
            step_i = jax.jit(make_train_step(cfg, opt_cfg))

            pi, oi = params, opt_state
            pc, oc = params, opt_state
            for s in range(3):
                pi, oi, mi = step_i(pi, oi, batch)
                pc, oc, err, mc = step_c(pc, oc, err, batch)
                li, lc = float(mi['loss']), float(mc['loss'])
                assert abs(li - lc) <= 0.02 * abs(li) + 1e-4, (s, li, lc)
            diffs = [float(jnp.abs(a - b).max())
                     for a, b in zip(jax.tree.leaves(pi), jax.tree.leaves(pc))]
            # AdamW normalizes update magnitude to ~lr, so int8 grad noise can
            # move any element by O(lr) per step: bound by the 3-step budget
            assert max(diffs) <= 2 * 3 * 1e-2, max(diffs)
            # error-feedback residual is alive and bounded
            enorm = max(float(jnp.abs(e).max()) for e in jax.tree.leaves(err))
            assert 0 < enorm < 1.0, enorm
            print("DP_COMPRESSED_OK", max(diffs))
            """
        )

    def test_train_driver_gates_on_flag_and_resumes(self):
        """launch.train engages the explicit dp-reduction when
        cfg.grad_compression is set and devices are available, logs the
        tuned-engine implicit-vs-explicit comparison, and — because the
        error-feedback residual is checkpointed — a kill/resume run
        reproduces the uninterrupted loss trajectory."""
        run_spmd(
            """
            import dataclasses, tempfile
            from repro import configs
            from repro.launch.train import train

            cfg = configs.get('gemma3-4b').reduced(
                vocab_size=512, d_model=128, num_heads=4, head_dim=32,
                num_kv_heads=2)
            cfg = dataclasses.replace(cfg, grad_compression=True)
            lines = []
            _, full = train(cfg, steps=4, batch=8, seq_len=16,
                            log=lines.append)
            assert len(full) == 4 and all(np.isfinite(full))
            joined = "\\n".join(lines)
            assert "explicit path ON" in joined, joined
            assert "dp-reduction model" in joined

            with tempfile.TemporaryDirectory() as d:
                train(cfg, steps=4, batch=8, seq_len=16, ckpt_dir=d,
                      ckpt_every=2, stop_after=2, log=lambda *_: None)
                _, resumed = train(cfg, steps=4, batch=8, seq_len=16,
                                   ckpt_dir=d, resume=True,
                                   log=lambda *_: None)
            np.testing.assert_allclose(resumed, full[2:], rtol=1e-6)
            print("TRAIN_DP_OK", full[-1])
            """
        )


class TestMoESPMD:
    def test_ep_dispatch_matches_local(self):
        """Expert-parallel all_to_all dispatch == single-device dispatch."""
        run_spmd(
            """
            from repro import configs
            from repro.models import moe as M
            from repro.models.transformer import DistContext
            import dataclasses
            cfg = configs.get('qwen3-moe-235b-a22b').reduced(
                num_experts=8, experts_per_token=2, moe_d_ff=32, d_model=64,
                capacity_factor=8.0)
            mesh = make_mesh((2, 4), ("data", "model"))
            ctx = DistContext(mesh=mesh, ep_axis="model", dp_axes=("data",), tp_axis="model")
            blk = M.init_moe_block(cfg, jax.random.PRNGKey(0), 1)
            blk = jax.tree.map(lambda x: x[0], blk)
            x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)
            out_local, _ = M.moe_block(x, blk, cfg, None)
            out_ep, _ = jax.jit(lambda x, b: M.moe_block(x, b, cfg, ctx))(x, blk)
            np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_local),
                                       atol=2e-4, rtol=2e-4)
            print("MOE_EP_OK")
            """
        )


class TestCompressionSPMD:
    def test_compressed_pmean_close_to_exact(self):
        run_spmd(
            """
            from repro.dist.compression import compressed_pmean
            mesh = make_mesh((8,), ("data",))
            rng = np.random.default_rng(0)
            g_all = jnp.asarray(rng.normal(size=(8, 4096)), jnp.float32)

            def body(g):
                mean, err = compressed_pmean(g[0], "data")
                return mean[None], err[None]

            f = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(P("data"),), out_specs=(P("data"), P("data"))))
            mean, err = f(g_all)
            exact = np.asarray(g_all).mean(0)
            got = np.asarray(mean)[0]
            # all shards agree
            assert np.allclose(np.asarray(mean), got[None], atol=1e-6)
            # int8 wire: relative error bounded by ~2/127 of the magnitude scale
            denom = np.abs(exact).max()
            assert np.abs(got - exact).max() <= 0.03 * denom, np.abs(got - exact).max()
            # error feedback residual bounded by local quantization step
            assert np.abs(np.asarray(err)).max() <= np.abs(np.asarray(g_all)).max() / 127.0 * 1.01
            print("COMPRESS_OK")
            """
        )

    def test_error_feedback_convergence(self):
        """EF-SGD on a quadratic: compressed gradients converge like exact."""
        run_spmd(
            """
            from repro.dist.compression import compressed_pmean
            mesh = make_mesh((8,), ("data",))
            rng = np.random.default_rng(1)
            target = jnp.asarray(rng.normal(size=(256,)), jnp.float32)

            def local_grad(x, shard):
                # each shard sees a noisy gradient; mean = true gradient
                noise = jax.random.normal(jax.random.PRNGKey(shard), (256,)) * 0.5
                return 2 * (x - target) + noise - noise  # deterministic per shard

            def step(x, err_all):
                def body(x_rep, err):
                    g = 2 * (x_rep - target)
                    mean, new_err = compressed_pmean(g, "data", err[0])
                    return mean[None], new_err[None]
                f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P("data")),
                                  out_specs=(P("data"), P("data")), check_vma=False)
                mean, err_all = f(x, err_all)
                return x - 0.05 * mean[0], err_all

            def loop(carry, _):
                x, err = carry
                x, err = step(x, err)
                return (x, err), None

            (x, err), _ = jax.jit(lambda: jax.lax.scan(
                loop, (jnp.zeros(256), jnp.zeros((8, 256))), None, length=120))()
            final = float(jnp.sum((x - target) ** 2))
            assert final < 1e-3, final
            print("EF_OK", final)
            """
        )


class TestMiniDryrun:
    def test_dryrun_path_on_host_mesh(self):
        """The real lower_cell path on an 8-device mesh, reduced config."""
        run_spmd(
            """
            import dataclasses
            from repro import configs
            from repro.launch import shapes
            from repro.launch.dryrun import lower_cell
            from repro.launch import hlo_analysis as H
            mesh = make_mesh((4, 2), ("data", "model"))
            cfg = configs.get('gemma3-4b').reduced(vocab_size=1024, d_model=256,
                num_heads=4, head_dim=64, num_kv_heads=2)
            cell = dataclasses.replace(shapes.SHAPES['train_4k'], seq_len=128,
                                       global_batch=8, microbatches=2)
            compiled, lowered = lower_cell(cfg, cell, mesh)
            stats = H.analyze(compiled.as_text(), 8)
            assert stats.flops > 1e8, stats.flops
            assert stats.collective_wire_bytes > 0
            mem = compiled.memory_analysis()
            assert mem.temp_size_in_bytes > 0
            print("DRYRUN_OK", int(stats.flops))
            """,
        )
