"""Helpers for the benchmark's CPU tests: small cells built from the
program's reduced preset, run with interpret-mode kernels on host devices
that the tests hand to the harness themselves."""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four host devices, for the tensor-parallel cell's mesh
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import run as R  # noqa: E402
from repro.configs import get_arch, list_archs  # noqa: E402
from repro.configs.base import register  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
TINY_ARCH = "qwen3-14b-smoke"      # the program's reduced qwen3-14b, `tiny-dense.json`
if TINY_ARCH not in list_archs():
    register(get_arch("qwen3-14b").reduced())

SERVE = {"driver": "serve_batch", "batch": 4, "prompt_len": 16,
         "out_len": {"median": 6, "sigma": 0.8, "min": 2, "max": 8}, "runtime": "shadow",
         "trace_s": 0.5}
E2E = [{"name": "tok_s", "unit": "tokens/s"}, {"name": "tpot_ms", "unit": "ms"},
       {"name": "setup_s", "unit": "s"}]


def tiny_cell(traffic: dict, limits: dict, name: str = "tiny") -> R.Cell:
    return R.Cell(name, {"name": name, "config": "tiny-dense", "traffic": "tiny", "chips": 1},
                  R.load_json(DATA / "tiny-dense.json"), dict(traffic), limits,
                  {"end_to_end": E2E, "per_layer": []})


def context(cell: R.Cell, seed: int = 0, seconds: float = 0.0, trace: bool = False) -> R.Ctx:
    """A run's context on the host devices, with the v5e's peaks."""
    return R.Ctx(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                 R.peaks_for("TPU v5 lite"))


def measure(cell: R.Cell, seconds: float = 0.5, seed: int = 2**33 + 17,
            trace: bool = False) -> dict:
    return R.measure(context(cell, seed, seconds, trace), t0=time.perf_counter())


@pytest.fixture
def serve_cell():
    return tiny_cell(SERVE, {"sample": 3, "max_token_gap": {"limit": 0.05}})
