"""Spans and scopes inside the served decode path: the serve loop's and
the runtime's `jax.profiler` host spans in a CPU profiler trace of
`greedy_decode`, and the model's `jax.named_scope`s in the decode
program's compiled text, read through the benchmark's scope map."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from bench.lib import attribution
from repro.configs import get_arch
from repro.models import build_model
from repro.runtime import Runtime
from repro.train.serve_loop import greedy_decode

B, P, S_MAX, STEPS = 2, 8, 16, 3


@pytest.fixture(scope="module")
def served():
    model = build_model(get_arch("qwen3-14b").reduced())
    params = model.init(jax.random.PRNGKey(0), jnp.bfloat16)
    return model, params


def _host_spans(path):
    """(name, start_ns, end_ns, stats) of every serve.*/runtime.* span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(glob.glob(f"{path}/**/*.xplane.pb", recursive=True)[0])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "runtime.")):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_profiler_trace_holds_the_serve_and_runtime_spans(served, tmp_path):
    model, params = served
    runtime = Runtime()
    ids = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P) % model.cfg.vocab_size
    with jax.profiler.trace(str(tmp_path)):
        greedy_decode(model, params, {"tokens": ids}, s_max=S_MAX, steps=STEPS,
                      cache_dtype=jnp.bfloat16, runtime=runtime)
    spans = _host_spans(tmp_path)

    def named(name):
        return [s for s in spans if s[0] == name]

    assert len(named("serve.compile")) == 2          # prefill and decode step
    assert len(named("serve.prefill")) == 1
    steps = named("serve.decode")
    assert sorted(s[3]["step_num"] for s in steps) == list(range(STEPS))
    for name in ("serve.submit", "serve.dispatch", "serve.sample", "serve.flush"):
        assert len(named(name)) == STEPS
        for _, lo, hi, _ in named(name):
            assert any(s_lo <= lo and hi <= s_hi for _, s_lo, s_hi, _ in steps)
    assert all(s[3]["batch"] == B for s in spans if s[0].startswith("serve."))
    for phase in ("runtime.plan", "runtime.launch", "runtime.record"):
        assert len(named(phase)) == STEPS
        for _, lo, hi, _ in named(phase):
            assert any(f_lo <= lo and hi <= f_hi for _, f_lo, f_hi, _ in named("serve.flush"))
    for _, lo, hi, _ in named("runtime.submit"):
        assert any(f_lo <= lo and hi <= f_hi for _, f_lo, f_hi, _ in named("serve.submit"))
    calls = runtime.telemetry.host_calls
    assert calls["submit"] == len(named("runtime.submit")) > 0
    assert calls["plan"] == calls["launch"] == calls["record"] == STEPS


def test_decode_program_carries_the_model_scopes(served):
    model, params = served
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    text = attribution.decode_program_text(model, shapes, B, P, S_MAX, jnp.bfloat16)
    scopes = attribution.op_scopes(text)
    names = set(scopes.values())
    layer = re.compile(r"decode_step/layers/while/body/(closed_call/)?block/")
    for part in ("attn/", "attn/kv_write/", "mlp/"):
        assert any(layer.search(n) and f"block/{part}" in n for n in names), part
    assert any("decode_step/lm_head/" in n for n in names)
    regions = {attribution.region(n) for n in names}
    assert {"block", "scan", "step", "none"} <= regions
    # every instruction of the text is in the map, whatever its metadata
    assert len(scopes) == len(re.findall(r"^\s*(?:ROOT )?%\S+ = ", text, re.M))


@pytest.mark.parametrize("op_name, where", [
    ("jit(decode_step)/decode_step/layers/while/body/closed_call/block/attn/dot_general",
     "block"),
    ("jit(decode_step)/decode_step/layers/while/body/dynamic_slice", "scan"),
    ("jit(decode_step)/decode_step/layers/while", "scan"),
    ("jit(decode_step)/decode_step/lm_head/dot_general", "step"),
    ("", "none"),
])
def test_region_of_a_scope(op_name, where):
    assert attribution.region(op_name) == where
