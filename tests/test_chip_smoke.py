"""chip_smoke.py's phases at tiny sizes on the CPU.

The phases run here with the Pallas kernels in interpret mode (the ops
wrappers choose it off TPU); the script itself refuses to run without a TPU.
"""

import sys
from pathlib import Path

import pytest

from repro import configs

from test_spmd import run_spmd

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def test_join_phase_matches_numpy():
    sizes, failures = chip_smoke.phase_join(3000, seed=1)
    assert failures == [], failures
    assert "rows=3000" in sizes


def test_groupby_phase_matches_numpy():
    sizes, failures = chip_smoke.phase_groupby(5000, 37, seed=2)
    assert failures == [], failures
    assert "groups=37" in sizes


@pytest.fixture(scope="module")
def trained():
    cfg = configs.get(chip_smoke.ARCH).reduced(num_layers=2)
    sizes, failures, params = chip_smoke.phase_train(cfg, steps=6, batch=2, seq_len=32)
    return cfg, sizes, failures, params


def test_train_phase_loss_falls(trained):
    _, sizes, failures, _ = trained
    assert failures == [], failures
    assert "[train step: implicit-dp jit over 1 device(s)]" in sizes


def test_serve_phase_decode_matches_teacher_forcing(trained):
    cfg, _, _, params = trained
    sizes, failures = chip_smoke.phase_serve(cfg, params, batch=2, prompt=16, new=4, seed=3)
    assert failures == [], failures
    assert "decoded=4" in sizes


def test_kernels_phase_matches_refs():
    sizes, failures = chip_smoke.phase_kernels(
        keys=2048, page=256, segments=8, heads=2, seq=256, head_dim=64, seed=4)
    assert failures == [], failures
    assert "mosaic=" in sizes


def test_distributed_phase_on_four_host_devices():
    out = run_spmd(
        """
        import sys
        sys.path.insert(0, ".")
        import chip_smoke
        sizes, failures = chip_smoke.phase_distributed(
            jax.devices()[:4], join_rows=500, groupby_rows=700, ngroups=29, seed=5)
        assert failures == [], failures
        print("DIST_OK", sizes)
        """
    )
    assert "DIST_OK mesh=(4,)" in out


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
