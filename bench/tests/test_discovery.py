"""A configuration, a traffic mix and a per-layer metric are found from
files and entries alone: a checkout that adds them, and changes no file
of the harness, runs the new cell with the new metric."""
from __future__ import annotations

import json
import shutil

from bench import run as R
from bench.tests.conftest import DATA, ROOT, measure

METRIC = '''"""Requests per batch, from the run record."""


def read(ctx, rec, t):
    return len(rec["batches"][0]["lens"])
'''


def _checkout(tmp_path):
    bench = tmp_path / "bench"
    for d in ("drivers", "lib", "configs", "traffic", "limits", "metrics"):
        shutil.copytree(ROOT / "bench" / d, bench / d)
    shutil.copy(ROOT / "bench" / "peaks.json", bench / "peaks.json")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return bench


def test_new_config_mix_and_metric_from_files_only(tmp_path):
    bench = _checkout(tmp_path)
    shutil.copy(DATA / "tiny-dense.json", bench / "configs" / "tiny-dense.json")
    (bench / "traffic" / "tiny-batch.json").write_text(json.dumps({
        "driver": "serve_batch", "batch": 2, "prompt_len": 8,
        "out_len": {"median": 3, "sigma": 0.5, "min": 2, "max": 4}, "trace_s": 0.2}))
    (bench / "limits" / "tiny-dense.tiny-batch.json").write_text(json.dumps(
        {"sample": 2, "max_token_gap": {"limit": 0.05}}))
    (bench / "metrics" / "batch_size.tiny.py").write_text(METRIC)
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-dense", "source": "test", "why": "test",
                         "file": "bench/configs/tiny-dense.json", "reduced": []})
    b["workloads"].append({"name": "tiny-dense.tiny-batch", "config": "tiny-dense",
                           "traffic": "tiny-batch", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] in ("tok_s", "tpot_ms"):
            m["workloads"].append("tiny-dense.tiny-batch")
    b["per_layer"].append({"name": "batch_size.tiny", "unit": "requests", "better": "higher",
                           "source": "program_counter", "layer": "serve loop",
                           "moves": "tok_s", "workloads": ["tiny-dense.tiny-batch"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = R.find_cell("tiny-dense.tiny-batch", root=tmp_path)
    assert cell.model["d_model"] == 128 and cell.traffic["batch"] == 2
    assert [m["name"] for m in cell.per_layer()] == ["batch_size.tiny"]
    assert {m["name"] for m in cell.end_to_end()} == {"tok_s", "tpot_ms", "setup_s"}
    res = measure(cell, seconds=0.1, seed=5, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {"batch_size.tiny": {"value": 2.0, "unit": "requests"}}


def test_unknown_names_are_errors(tmp_path):
    import pytest

    with pytest.raises(KeyError):
        R.find_cell("no-such.cell")
    with pytest.raises(FileNotFoundError):
        R.load_module("metrics", "no_such_metric")
    with pytest.raises(KeyError):
        R.peaks_for("TPU v99")
