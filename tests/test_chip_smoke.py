"""``chip_smoke.py`` on the CPU: its phases at a tiny size, with the
Pallas kernels in interpret mode, and its refusal to run without a TPU."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("dist", ["uniform", "normal"])
def test_main_phase_tunes_applies_and_matches_direct(dist):
    rec = chip_smoke.phase_main(dist, 1024, samples=128, backend="pallas")
    assert rec["dispatched"]["apply"] == "pallas"
    assert rec["err_vs_direct"]["pointwise"] <= chip_smoke.F32_TOL
    assert rec["tile_trials_host_s"]       # the sweep reported its trials


def test_log_phase_agrees_with_reference_backend_and_direct():
    rec = chip_smoke.phase_log(2048, samples=64, backend="pallas")
    assert rec["pallas_vs_reference"]["normwise"] < chip_smoke.LOG_AGREE
    assert rec["re_err_vs_direct"] <= chip_smoke.F32_TOL


def test_batched_phase_checks_every_row():
    rec = chip_smoke.phase_batched(300, 2, backend="pallas")
    assert rec["dispatched"]["apply_batched"] == "pallas"
    assert len(rec["err_vs_direct_rows"]) == 2


def test_serving_phase_serves_a_wave_on_the_fast_path():
    rec = chip_smoke.phase_serving(64, 128, 5, checked=2, backend="pallas")
    assert rec["statuses"] == {"ok": 5}
    assert rec["backends"] == {"pallas": 5}
    # the warmed executables are exactly the ones the wave dispatched
    assert rec["executables"]


def test_wave_shapes_follow_serve_chunking():
    from repro.serve import BucketLattice
    lattice = BucketLattice.geometric(64, 256)       # 64, 128, 256
    sizes = [10] * 9 + [100] * 3 + [200]
    assert chip_smoke._wave_shapes(lattice, sizes, 8) == [
        (64, 1), (64, 8), (128, 4), (256, 1)]


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
