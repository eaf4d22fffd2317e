"""chip_smoke.py's phases at tiny size on the CPU, and its refusal to
report success off a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_phase_icu_tiny(smoke):
    out = smoke.phase_icu(patients=6)
    assert out["lower_bound"] <= out["ours"]
    assert all(out["ours"] <= v for v in out["baselines"].values())
    # off the chip the platform picks the reference cell, not the kernel
    assert out["device_search"] is False
    for wl in out["workloads"].values():
        assert wl["kernel_in_hlo"] is False
        assert wl["logits_max_abs_err"] <= smoke.LOGITS_ATOL
        assert wl["logits_max_abs"] > 0


def test_phase_fleet_tiny(smoke):
    out = smoke.phase_fleet(wards=4, patients=32)
    for fleet in out.values():
        assert fleet["wards"] == 4
        assert fleet["fleet_true"] <= fleet["naive_fleet_true"]
        assert fleet["gap_closed"] > 0
        assert fleet["naive_max_rel_err"] <= smoke.OBJECTIVE_RTOL
        assert fleet["sweep_max_rel_err"] <= smoke.OBJECTIVE_RTOL
        assert fleet["regimes"][0] == "round"
    assert out["contention"]["contention_gap"] > 1
    assert out["contention"]["regimes"] == ["round", "pass"]


def test_phase_metro_tiny(smoke):
    out = smoke.phase_metro(wards=2, hours=0.5)
    assert set(out["policies"]) == {"greedy", "tabu", "fleet"}
    assert out["device_searches"] > 0
    assert all(p["completions"] > 0 for p in out["policies"].values())


@pytest.mark.parametrize("alone", [False, True], ids=["repo", "alone"])
def test_script_fails_off_chip(tmp_path, alone):
    """Run as a script on the CPU, from the checkout or copied into an
    otherwise empty directory, it exits nonzero and prints no result."""
    script = SCRIPT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
        env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert ("No module named 'repro'" if alone else "needs a TPU") \
        in out.stderr
