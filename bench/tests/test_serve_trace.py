"""The readers of the program's own spans and scopes, on a trace recorded
on a TPU v5e by `record_serve_trace.py`: six `greedy_decode` steps of the
tiny model with the shadow runtime (`serve.xplane.pb`), and the decode
program's compiled text (`serve_decode.hlo.txt`)."""
from __future__ import annotations

import pytest

from bench import run as R
from bench.lib import attribution
from bench.lib import trace as tr
from bench.tests.conftest import DATA, SERVE, context, tiny_cell

DEV = "/device:TPU:0"
STEPS = 6


@pytest.fixture(scope="module")
def t():
    return tr.load(str(DATA / "serve.xplane.pb"))


@pytest.fixture(scope="module")
def text():
    return (DATA / "serve_decode.hlo.txt").read_text()


def spans(t, name):
    return sorted((h for h in t.host if h.name == name), key=lambda h: h.start)


def test_the_trace_holds_the_serve_and_runtime_spans(t):
    assert len(spans(t, "serve.decode")) == STEPS
    for name in ("serve.submit", "serve.dispatch", "serve.sample", "serve.flush",
                 "runtime.plan", "runtime.launch", "runtime.record"):
        assert len(spans(t, name)) == STEPS, name
    assert len(spans(t, "runtime.submit")) == 4 * STEPS     # QKV, O, up+gate, down
    assert len(spans(t, "serve.compile")) == 2 and len(spans(t, "serve.prefill")) == 1


def test_scope_map_finds_every_decode_op(t, text):
    scopes = attribution.op_scopes(text)
    progs, ops = attribution.decode_ops(t)[DEV]
    assert len(progs) == STEPS and ops
    assert {e.name for e in ops} <= set(scopes)
    shares = attribution.time_by_region(progs, ops, scopes)
    assert "missing" not in shares
    assert shares == pytest.approx({"block": 0.51959, "scan": 0.14632, "step": 0.10212,
                                    "none": 0.13346}, abs=1e-5)
    writes = [e for e in ops if "/block/attn/kv_write/" in scopes[e.name]]
    assert len(writes) == 2 * 2 * STEPS                      # K and V, two layers


def test_scan_plumbing_share_reads_the_recorded_program(t, text, monkeypatch):
    monkeypatch.setattr(attribution, "decode_program_text", lambda *a, **k: text)
    cell = tiny_cell(SERVE, {"sample": 3, "max_token_gap": {"limit": 0.05}})
    rec = {"s_max": SERVE["prompt_len"] + SERVE["out_len"]["max"] + 1}
    read = R.load_module("metrics", "scan_plumbing_share.decode").read
    assert read(context(cell), rec, t) == pytest.approx(14.632357, abs=1e-5)


def test_runtime_idle_share_on_the_recorded_trace(t):
    read = R.load_module("metrics", "runtime_idle_share.serve").read
    first = min(e.start for e in tr.per_device(t, r"decode_step", modules=True)[DEV])
    lo, hi = first, t.window[1]
    runtime = [h for h in t.host if h.name.startswith("runtime.")]
    idle = attribution.idle_under(t.ops[DEV], runtime, lo, hi)
    assert idle == pytest.approx(0.00137782, abs=1e-8)
    assert read(None, {}, t) == pytest.approx(100 * idle / (hi - lo))
    assert 0 < read(None, {}, t) < R.load_module("metrics", "idle_share.serve").read(None, {}, t)
    # the split by innermost span covers the stretch's whole idle time
    split = attribution.idle_by_span(
        t.ops[DEV], [h for h in t.host if h.name.startswith(("serve.", "runtime."))], lo, hi)
    assert sum(split.values()) == pytest.approx(tr.idle_share(t, since=lo) * (hi - lo))
    assert sum(v for k, v in split.items() if k.startswith("runtime.")) == pytest.approx(idle)


def test_host_and_device_clocks(t):
    """Each decode program lies between the dispatch spans before and
    after the one that enqueued it, so the association is plain; but the
    device's clock reads about a millisecond early against the host's:
    from the second step on, a program starts 0.49-0.73 ms before the
    `serve.dispatch` span that enqueued it, and 0.97-1.10 ms before the
    runtime's own `TpuLoadedExecutable::ExecuteLaunch` for it."""
    progs = sorted(tr.per_device(t, r"decode_step", modules=True)[DEV], key=lambda e: e.start)
    disp = spans(t, "serve.dispatch")
    assert len(progs) == len(disp) == STEPS
    for k, (p, d) in enumerate(zip(progs, disp)):
        assert k == 0 or disp[k - 1].end < p.start
        assert k == STEPS - 1 or p.end < disp[k + 1].start
    lead = [d.start - p.start for p, d in zip(progs, disp)]
    assert lead[0] < 0 < min(lead[1:])
    assert max(lead[1:]) == pytest.approx(0.000728, abs=1e-6)
    launches = spans(t, "TpuLoadedExecutable::ExecuteLaunch")
    for p, d in zip(progs[1:], disp[1:]):
        launch = [x for x in launches if d.start <= x.start < d.end]
        assert len(launch) == 1 and 0.00097 < launch[0].start - p.start < 0.00111
