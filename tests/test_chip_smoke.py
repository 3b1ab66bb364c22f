"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
runtime phase (here at reduced width, kernels in interpret mode) passes
only when no launch faulted or fell back."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_arch

ROOT = Path(__file__).resolve().parents[1]


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_runtime_phase_reduced_has_no_faults_or_fallbacks():
    out = _smoke().runtime_phase(get_arch("stablelm-3b").reduced())
    assert out["faults"] == {} and out["fallbacks"] == {}
    assert out["modes"].get("grouped", 0) + out["modes"].get("ragged", 0) > 0
    assert out["tiles"]


def test_runtime_phase_fails_on_a_fault(monkeypatch):
    """A launch that faults and completes on a lower rung of the fallback
    ladder still fails the smoke: the ladder must not hide the device."""
    import repro.runtime
    from repro.runtime import FaultInjector, FaultRule, Runtime

    class Faulty(Runtime):
        def __init__(self, *a, **k):
            inj = FaultInjector(rules=[FaultRule("raise", 1.0, max_faults=1)])
            super().__init__(*a, fault_injector=inj, **k)

    monkeypatch.setattr(repro.runtime, "Runtime", Faulty)
    smoke = _smoke()
    with pytest.raises(smoke.SmokeFailure, match="faults"):
        smoke.runtime_phase(get_arch("stablelm-3b").reduced())
