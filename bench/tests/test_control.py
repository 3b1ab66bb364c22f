"""The control fails the comparison: the reference in the program's place
with its matrix products in float8 e4m3, read at a size a test can hold
(on the chip, `bench/calibrate.py` reads it at each cell's own size)."""
from __future__ import annotations

import pytest

from bench import calibrate as C
from bench import run as R
from bench.tests.conftest import context, tiny_cell

SERVE = {"driver": "serve_batch", "batch": 4, "prompt_len": 16,
         "out_len": {"median": 12, "sigma": 0.5, "min": 4, "max": 24}, "runtime": "shadow",
         "trace_s": 0.5}


@pytest.mark.parametrize("seed", [101, 102, 103])
def test_served_control_fails(seed):
    limit = 0.05
    cell = tiny_cell(SERVE, {"sample": 4, "max_token_gap": {"limit": limit}})
    r = C.serve_readings(context(cell, seed), R.load_module("drivers", "serve_batch"))
    assert r["program"] <= limit < r["control"], r
