"""chip_smoke.py's control flow at toy sizes on the CPU: every phase
function runs with the Pallas kernels in interpret mode, so a broken phase
is found here before chip time is spent on it; and the script itself
refuses to run without a chip."""
import json
import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402

_GBDT_TOY = dict(n_rows=6000, n_features=4, n_iters=2, max_depth=2,
                 n_score_rows=4096, auc_floor=0.7)


@pytest.fixture
def interpreted_hist(monkeypatch):
    """Route histograms through the real kernels, interpreted."""
    monkeypatch.setenv("MMLSPARK_TPU_HIST", "pallas")
    monkeypatch.setenv("MMLSPARK_TPU_HIST_INTERPRET", "1")


def test_kernel_phase_toy(interpreted_hist, monkeypatch, capsys):
    sizes = dict(hist_rows=300, hist_features=3, hist_nodes=(2,),
                 hist_bins=(64,), flash_seq=128, flash_heads=1,
                 flash_head_dims=(64,), flash_dtypes=("float32",))
    rep = chip_smoke.kernel_phase(**sizes)
    assert rep["rows"] == 1 + 4 and rep["failed"] == []
    out = capsys.readouterr().out
    assert "hist m2 B64: {'route': 'joint:lo16'" in out
    assert json.loads(out.splitlines()[-1])["phase"] == "kernels"

    # a failing row does not hide the rows after it, and fails the run
    def half_broken(m, *a):
        if m == 2:
            raise FloatingPointError("injected")
        return {"route": "faked"}
    monkeypatch.setattr(chip_smoke, "_hist_row", half_broken)
    sizes.update(hist_nodes=(2, 8), flash_head_dims=())
    with pytest.raises(RuntimeError, match="1 of 2 kernel rows failed"):
        chip_smoke.kernel_phase(**sizes)
    out = capsys.readouterr().out
    assert "hist m2 B64: FAILED FloatingPointError: injected" in out
    assert "hist m8 B64: {'route': 'faked'}" in out


def test_gbdt_phase_toy(interpreted_hist, monkeypatch):
    # one device: the fit the one-chip run takes (the sharded variant
    # below costs two fits)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    rep = chip_smoke.gbdt_phase(**_GBDT_TOY)
    assert rep["routes"] == {"joint": 2}
    assert rep["trees"] == 2 and rep["auc"] >= 0.7
    assert rep["mosaic_calls"] == 0   # interpreted here, compiled on chip
    # an XLA-scatter level is exactly the silent fallback the smoke run
    # exists to catch, and so is a fit that reports no route at all
    for routes in ({"joint": 4, "xla": 1}, {}):
        monkeypatch.setattr(chip_smoke, "_hist_routes_taken", lambda: routes)
        with pytest.raises(AssertionError, match="histogram routes"):
            chip_smoke.gbdt_phase(**_GBDT_TOY)


@pytest.mark.slow
def test_gbdt_phase_toy_sharded(interpreted_hist):
    """8 virtual devices: the default estimator takes the sharded fit and
    the phase compares it with the one-device fit."""
    rep = chip_smoke.gbdt_phase(**_GBDT_TOY)
    assert rep["routes"] == {"joint": 2}
    assert abs(rep["auc_one_device"] - rep["auc"]) <= 1e-3


def test_lm_phase_toy():
    assert dict(chip_smoke.lm_mesh(1).shape) == {"data": 1, "pipe": 1}
    rep = chip_smoke.lm_phase(n_layers=2, d_model=32, n_heads=2, d_ff=64,
                              vocab=64, seq=128, n_devices=4)
    assert rep["mesh"] == {"data": 1, "pipe": 2, "model": 2}
    assert rep["batch"] == [2, 64]    # one microbatch per pipeline stage
    assert rep["losses"][0] > rep["losses"][2]
    assert rep["compiles_per_step"][2] == 0


def test_script_refuses_to_run_without_a_chip():
    """`python chip_smoke.py` on the CPU exits non-zero, names the missing
    chip, prints no result line and compiles nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(_REPO,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         cwd=_REPO, timeout=120)
    assert res.returncode != 0
    assert "platform=cpu" in res.stdout and '"ok"' not in res.stdout
    assert "not a TPU chip" in res.stderr
    assert "phase" not in res.stdout
