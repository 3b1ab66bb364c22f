"""The attribution of device time to the program's names
(`bench/lib/attribution.py`) and the two metrics that read it, on traces
built by hand: idle time under host spans, and decode time by scope with
the decode program compiled here from the tiny cell's shapes."""
from __future__ import annotations

import pytest

from bench import run as R
from bench.lib import attribution
from bench.lib.trace import Event, Trace
from bench.tests.conftest import SERVE, context, tiny_cell

DEV = "/device:TPU:0"


def ev(name, start, end):
    return Event(name, start, end, name)


def test_gaps_idle_under_and_idle_by_span():
    ops = [ev("a", 0.1, 0.3), ev("b", 0.25, 0.4), ev("c", 0.6, 0.7)]
    assert attribution.gaps(ops, 0.0, 1.0) == pytest.approx(
        [(0.0, 0.1), (0.4, 0.6), (0.7, 1.0)])
    spans = [ev("runtime.plan", 0.35, 0.5), ev("runtime.launch", 0.5, 0.55),
             ev("runtime.plan", 0.9, 1.2)]
    # idle and inside a span: 0.4-0.55 and 0.9-1.0
    assert attribution.idle_under(ops, spans, 0.0, 1.0) == pytest.approx(0.25)
    assert attribution.idle_under(ops, spans, 0.45, 1.0) == pytest.approx(0.2)
    nested = spans + [ev("serve.flush", 0.3, 0.58)]
    split = attribution.idle_by_span(ops, nested, 0.0, 1.0)
    # the innermost span open: runtime.* inside serve.flush
    assert split == pytest.approx({"none": 0.1 + 0.02 + 0.2, "runtime.plan": 0.1 + 0.1,
                                   "runtime.launch": 0.05, "serve.flush": 0.03})
    assert sum(split.values()) == pytest.approx(0.1 + 0.2 + 0.3)


def _trace(decode_ops, host=()):
    t = Trace(window=(0.0, 1.0))
    t.modules[DEV] = [ev("jit_decode_step", 0.2, 0.8)]
    t.ops[DEV] = list(decode_ops)
    t.host = list(host)
    return t


def test_runtime_idle_share_reads_idle_under_runtime_spans():
    read = R.load_module("metrics", "runtime_idle_share.serve").read
    ops = [ev("fusion.1", 0.2, 0.5), ev("fusion.2", 0.6, 0.8)]
    assert read(None, {}, _trace(ops)) is None           # no runtime spans
    host = [ev("serve.flush", 0.45, 0.75), ev("runtime.plan", 0.5, 0.7),
            ev("runtime.submit", 0.9, 0.95)]
    # stretch 0.2-1.0; idle 0.5-0.6 and 0.8-1.0; under runtime.* 0.1 + 0.05
    assert read(None, {}, _trace(ops, host)) == pytest.approx(100 * 0.15 / 0.8)


@pytest.mark.parametrize("chips", [1, 4])
def test_scan_plumbing_share_maps_traced_ops_to_scopes(chips):
    cell = tiny_cell(SERVE, {"sample": 3, "max_token_gap": {"limit": 0.05}})
    if chips == 4:
        cell.entry["chips"] = 4
        cell.config["mesh"] = {"data": 1, "model": 4}
    ctx = context(cell)
    rec = {"s_max": SERVE["prompt_len"] + SERVE["out_len"]["max"] + 1}
    driver = R.load_module("drivers", "serve_batch")
    model, _, shapes, shardings = driver.build(ctx)
    import jax
    import jax.numpy as jnp

    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                          shapes, shardings)
    scopes = attribution.op_scopes(attribution.decode_program_text(
        model, params, SERVE["batch"], SERVE["prompt_len"], rec["s_max"], jnp.bfloat16))
    by = {}
    for name, op_name in scopes.items():
        by.setdefault(attribution.region(op_name), name)
    assert {"block", "scan", "step"} <= set(by)
    ops = [ev(by["block"], 0.2, 0.4), ev(by["scan"], 0.4, 0.55), ev("while.9", 0.2, 0.8),
           ev("not-in-the-text.1", 0.55, 0.6), ev(by["step"], 0.6, 0.7)]
    read = R.load_module("metrics", "scan_plumbing_share.decode").read
    assert read(ctx, rec, _trace(ops)) == pytest.approx(100 * 0.15 / 0.6)
    shares = attribution.time_by_region(*attribution.decode_ops(_trace(ops))[DEV], scopes)
    assert shares == pytest.approx({"block": 0.2 / 0.6, "scan": 0.15 / 0.6,
                                    "missing": 0.05 / 0.6, "step": 0.1 / 0.6})
