"""The served driver end to end at a reduced size (interpret-mode kernels
on the CPU), and the faults that the comparison has to catch."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from bench import run as R
from bench.tests.conftest import SERVE, measure, tiny_cell


def test_serve_dense_is_correct(serve_cell):
    res = measure(serve_cell)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tok_s", "tpot_ms", "setup_s"}
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_traced_run_reads_the_untraced_batches(serve_cell):
    """The profiler runs for `trace_s` from the first batch's start; the
    window does not close on that batch, and the host-clock metrics read
    the untraced batches after it."""
    serve_cell.bench["per_layer"] = [
        {"name": n, "unit": u, "moves": "tok_s"}
        for n, u in (("mfu.serve", "%"), ("serve_compile_ms", "ms"),
                     ("flush_us.serve", "us"), ("idle_share.serve", "%"))]
    res = measure(serve_cell, seconds=0.1, trace=True)
    assert res["correct"], res["checks"]
    # host devices leave no device planes, so the device's own metrics
    # find nothing to read
    assert set(res["metrics"]) == {"mfu.serve", "serve_compile_ms", "flush_us.serve"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["window_s"] > 0.5 and "breakdown" in res


def test_token_altered_where_produced_is_caught(serve_cell, monkeypatch):
    """Every decode step's chosen token moved to the next id."""
    from repro.models.model import Model

    step = Model.decode_step

    def altered(self, params, tokens, cache, cache_len):
        logits, cache, n = step(self, params, tokens, cache, cache_len)
        nxt = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
        bump = 1e4 * (jnp.arange(logits.shape[-1]) == nxt[..., None])
        return logits + bump.astype(logits.dtype), cache, n

    monkeypatch.setattr(Model, "decode_step", altered)
    res = measure(serve_cell)
    assert not res["correct"], res["checks"]


def test_step_that_returns_its_state_unchanged_is_caught(serve_cell, monkeypatch):
    """Every decode step hands back the cache it was given, so no token
    after the prompt is ever written to it."""
    from repro.models.model import Model

    step = Model.decode_step

    def stale(self, params, tokens, cache, cache_len):
        logits, _, n = step(self, params, tokens, cache, cache_len)
        return logits, cache, n

    monkeypatch.setattr(Model, "decode_step", stale)
    res = measure(serve_cell)
    assert not res["correct"], res["checks"]


def test_no_chip_exits_before_measuring(capsys):
    rc = R.main(["--workload", "stablelm-3b.serve-batch", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 3 and "platform=cpu" in out.out and "no TPU" in out.err
    assert not [ln for ln in out.out.splitlines() if ln.startswith("{")]


def test_exchange_between_chips_left_out_is_caught(monkeypatch):
    """Tensor parallel over four devices with the MLP's all-reduce left
    out: each chip keeps only its own quarter of the down projection's sum."""
    import repro.models.blocks as blocks

    cell = tiny_cell(SERVE, {"sample": 3, "max_token_gap": {"limit": 0.05}})
    cell.entry["chips"] = 4
    cell.config = copy.deepcopy(cell.config)
    cell.config["mesh"] = {"data": 1, "model": 4}
    assert measure(cell)["correct"]

    def local_only(p, x):
        q = p["down"].shape[0] // 4
        h = jax.nn.silu(x @ p["gate"][:, :q]) * (x @ p["up"][:, :q])
        return h @ p["down"][:q]

    monkeypatch.setattr(blocks, "mlp_apply", local_only)
    res = measure(cell)
    assert not res["correct"], res["checks"]
