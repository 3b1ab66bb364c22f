"""The reduction from a profiler trace to numbers, on a small trace
recorded on a TPU v5e by `record_trace.py`: two rounds of a jitted step
(`jit_decode_step`), one Pallas GEMM and one flash-attention kernel, each
in a host span, all inside the `bench.traced` span."""
from __future__ import annotations

import pytest

from bench.lib import trace as tr
from bench.tests.conftest import DATA


@pytest.fixture(scope="module")
def t():
    return tr.load(str(DATA / "small.xplane.pb"))


def test_planes_window_and_names(t):
    assert sorted(t.ops) == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(0.562814999, abs=1e-9)   # the bench.traced span
    names = [e.name for e in t.ops["/device:TPU:0"]]
    assert names.count("goldyloc_gemm_128x256x256.1") == 2
    assert names.count("goldyloc_flash_bq128_bkv128.1") == 2
    assert all(" = " not in n and not n.startswith("%") for n in names)
    mods = [e.name for e in t.modules["/device:TPU:0"]]
    assert mods.count("jit_decode_step") == 1


def test_busy_is_the_union_of_op_intervals(t):
    evs = t.ops["/device:TPU:0"]
    # brute force over 10 ns ticks
    lo = min(e.start for e in evs)
    ticks = set()
    for e in evs:
        ticks.update(range(round((e.start - lo) * 1e8), round((e.end - lo) * 1e8)))
    assert tr.busy_s(t) == pytest.approx(len(ticks) * 1e-8, rel=1e-3)
    assert tr.idle_share(t) == pytest.approx(1 - tr.busy_s(t) / t.window_s)
    assert 0 < tr.busy_s(t) < t.window_s


def test_idle_share_from_a_point_on(t):
    step = tr.per_device(t, r"decode_step", modules=True)["/device:TPU:0"][0]
    hi = t.window[1]
    assert 0 < tr.busy_s(t, since=step.start) < tr.busy_s(t)
    assert tr.idle_share(t, since=step.start) == pytest.approx(
        1 - tr.busy_s(t, since=step.start) / (hi - step.start))
    assert tr.idle_share(t, since=t.window[0] - 1.0) == pytest.approx(tr.idle_share(t))


def test_programs_cut_by_the_window_are_left_out():
    from types import SimpleNamespace as NS

    def ev(name, start_s, dur_s):
        return NS(name=name, start_ns=int(start_s * 1e9), duration_ns=int(dur_s * 1e9),
                  stats=())

    line = NS(events=[ev("jit_decode_step(1)", 0.5, 1.0), ev("jit_decode_step(2)", 2.0, 1.0),
                      ev("jit_decode_step(3)", 3.5, 1.0)])
    whole = tr._events(line, 1.0, 4.0, whole=True)
    assert [(e.start, e.end) for e in whole] == [(2.0, 3.0)]
    cut = tr._events(line, 1.0, 4.0)
    assert [(e.start, e.end) for e in cut] == [(1.0, 1.5), (2.0, 3.0), (3.5, 4.0)]


def test_kernels_are_found_by_their_own_name(t):
    flash = tr.matching(t.ops["/device:TPU:0"], r"goldyloc_flash")
    assert [e.name for e in flash] == ["goldyloc_flash_bq128_bkv128.1"] * 2
    gemm = tr.matching(t.ops["/device:TPU:0"], r"goldyloc_\w*gemm")
    assert len(gemm) == 2 and all(e.dur > 0 for e in gemm)
    step = tr.per_device(t, r"decode_step", modules=True)["/device:TPU:0"]
    inside = tr.within(t.ops["/device:TPU:0"], step)
    assert inside and all(step[0].start <= e.start < step[0].end for e in inside)


def test_breakdown(t):
    top = tr.top_ops(t)
    assert top[0][0] == "goldyloc_flash_bq128_bkv128"
    assert top[0][1] == pytest.approx(sum(e.dur for e in tr.matching(
        t.ops["/device:TPU:0"], "goldyloc_flash")))
    gaps = tr.idle_gaps(t)
    assert 1 <= len(gaps) <= 10 and all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_union_of_overlapping_events():
    ev = [tr.Event("a", 0.0, 2.0), tr.Event("b", 1.0, 3.0), tr.Event("c", 5.0, 6.0)]
    assert tr.union_s(ev) == 4.0
    assert tr.bare("%fusion.3 = bf16[2]{0} fusion(bf16[2]{0} %goldyloc_flash.1)") == "fusion.3"
    assert tr.bare("jit_decode_step(294169904297923677)") == "jit_decode_step"
