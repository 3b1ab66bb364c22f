"""Chaos hardening: fault injection, fallback ladder, quarantine — §18.

The contract under test is the paper-serving runtime's survival story:
a deterministic, seed-keyed `FaultInjector` makes specific launches
raise / go NaN / stall, and the runtime must (a) complete EVERY request
bitwise-equal to the fault-free run by walking the fallback ladder
(planned → retry → legacy → reference), (b) quarantine a GO entry after
K consecutive strikes with full cache hygiene, and (c) change NOTHING —
bitwise — when injection is disabled.  Operands are integer-valued f32,
so every kernel, grouping, and ladder rung produces identical bits and
"bitwise-equal" is a meaningful oracle rather than a tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ConcurrencyController, GemmDesc, GemmRequest, GOLibrary
from repro.core.cost_model import CostCalibrator
from repro.runtime import (
    CircuitBreaker,
    FaultInjector,
    FaultRule,
    InjectedFault,
    LaunchStall,
    NonFiniteOutput,
    Runtime,
    RuntimeConfig,
)
from repro.runtime.faults import fault_kind
from tests.hypothesis_compat import given, settings, st

D1 = GemmDesc(32, 128, 128, dtype="f32")
D2 = GemmDesc(64, 128, 128, dtype="f32")


def _ints(key, shape):
    # Integer-valued f32 operands: exact in f32 accumulation, so every
    # execution path yields bit-identical results.
    return jax.random.randint(key, shape, -4, 5).astype(jnp.float32)


def _req(d: GemmDesc, i: int = 0) -> GemmRequest:
    ka, kb = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(7), i))
    return GemmRequest(desc=d, a=_ints(ka, (d.M, d.K)), b=_ints(kb, (d.K, d.N)))


def _runtime(inj: FaultInjector | None = None, **cfg_kw) -> Runtime:
    cfg_kw.setdefault("window_s", 0.0)
    cfg_kw.setdefault("execute", True)
    cfg_kw.setdefault("interpret", False)   # CPU: fast XLA reference path
    ctrl = ConcurrencyController(library=GOLibrary())
    return Runtime(ctrl, RuntimeConfig(**cfg_kw), fault_injector=inj)


def _serve(rt: Runtime, n: int = 3):
    tickets = [rt.submit(_req(D1, i), now=0.0) for i in range(n)]
    launches = rt.drain(now=1.0)
    return tickets, launches


# --------------------------------------------------------- injector unit
def test_injection_decisions_are_deterministic():
    rules = (FaultRule("raise", 0.5),)
    a, b = FaultInjector(rules, seed=3), FaultInjector(rules, seed=3)
    seq_a = [a.decide("gemm", "ck", "tk") is not None for _ in range(64)]
    seq_b = [b.decide("gemm", "ck", "tk") is not None for _ in range(64)]
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)
    assert a.log == b.log
    c = FaultInjector(rules, seed=4)
    seq_c = [c.decide("gemm", "ck", "tk") is not None for _ in range(64)]
    assert seq_c != seq_a                   # seed keys the whole schedule


def test_rules_scope_by_family_class_and_tile():
    r = FaultRule("raise", 1.0, family="gemm", class_key="c1", tile_key="t1")
    assert r.matches("gemm", "c1", "t1")
    assert not r.matches("flash_attention", "c1", "t1")
    assert not r.matches("gemm", "c2", "t1")
    assert not r.matches("gemm", "c1", "t2")
    inj = FaultInjector((r,), seed=0)
    assert inj.decide("mamba_scan", "c1", "t1") is None
    assert inj.decide("gemm", "c1", "t1") is r


def test_max_faults_caps_deliveries():
    inj = FaultInjector((FaultRule("raise", 1.0, max_faults=2),), seed=0)
    hits = [inj.decide("gemm", "c", "t") is not None for _ in range(5)]
    assert hits == [True, True, False, False, False]
    assert len(inj.log) == 2
    assert [i.ordinal for i in inj.log] == [0, 1]


def test_fault_kind_buckets():
    assert fault_kind(LaunchStall("x")) == "stall"
    assert fault_kind(NonFiniteOutput("x")) == "nan"
    assert fault_kind(InjectedFault("x")) == "raise"
    assert fault_kind(ValueError("x")) == "error"   # genuine kernel error


def test_stall_advances_injectable_clock():
    seen = []
    inj = FaultInjector((FaultRule("stall", 1.0, stall_s=2.5e-3),),
                        seed=0, advance=seen.append)
    with pytest.raises(LaunchStall):
        inj._deliver(inj.decide("gemm", "c", "t"), [], [0])
    assert seen == [2.5e-3]


# ---------------------------------------------------------- breaker unit
def test_breaker_quarantines_on_kth_consecutive_strike():
    br = CircuitBreaker(strikes=3, cooldown_s=1.0)
    assert not br.strike("gemm", "c", "t", now=0.0)
    assert not br.strike("gemm", "c", "t", now=0.0)
    assert br.strike("gemm", "c", "t", now=0.0)     # K-th: True exactly once
    assert br.is_quarantined("gemm", "c", "t")
    assert not br.strike("gemm", "c", "t", now=0.0)  # already out
    assert br.quarantine_count == 1


def test_breaker_success_resets_consecutive_counter():
    br = CircuitBreaker(strikes=2)
    br.strike("gemm", "c", "t", now=0.0)
    br.succeed("gemm", "c", "t")                    # healthy launch resets
    assert not br.strike("gemm", "c", "t", now=0.0)
    assert not br.is_quarantined("gemm", "c", "t")


def test_breaker_half_open_release_and_requarantine():
    br = CircuitBreaker(strikes=3, cooldown_s=1.0)
    for _ in range(3):
        br.strike("gemm", "c", "t", now=0.0)
    assert br.release_due(now=0.5) == []            # cooldown not elapsed
    assert br.release_due(now=1.0) == [("gemm", "c", "t")]
    assert not br.is_quarantined("gemm", "c", "t")
    # Half-open probation: ONE more failure re-quarantines immediately...
    assert br.strike("gemm", "c", "t", now=2.0)
    assert br.release_due(now=3.0) == [("gemm", "c", "t")]
    # ...while a success clears the breaker entirely.
    br.succeed("gemm", "c", "t")
    assert not br.active


# ------------------------------------------------------- fallback ladder
def _fault_free_results(n: int = 3):
    rt = _runtime()
    tickets, _ = _serve(rt, n)
    return [np.asarray(t.result) for t in tickets]


def test_retry_rung_completes_bitwise_equal():
    inj = FaultInjector((FaultRule("raise", 1.0, max_faults=1),), seed=0)
    rt = _runtime(inj, quarantine_strikes=10)
    tickets, launches = _serve(rt)
    for tk, want in zip(tickets, _fault_free_results()):
        np.testing.assert_array_equal(np.asarray(tk.result), want)
    assert dict(rt.telemetry.faults) == {"raise": 1}
    assert dict(rt.telemetry.fallbacks) == {"retry": 1}
    fb = [ln for ln in launches if ln.fallback == "retry"]
    assert len(fb) == 1
    # The failed attempt consumed modeled device time (§18.2).
    assert fb[0].penalty_s == fb[0].plan.modeled_time_s > 0.0


def test_legacy_rung_after_retries_exhausted():
    # planned + 1 retry both injected; the legacy (isolated-tile) replan
    # is attempt #3, past max_faults=2, so it runs clean.
    inj = FaultInjector((FaultRule("raise", 1.0, max_faults=2),), seed=0)
    rt = _runtime(inj, max_retries=1, quarantine_strikes=10)
    tickets, launches = _serve(rt)
    for tk, want in zip(tickets, _fault_free_results()):
        np.testing.assert_array_equal(np.asarray(tk.result), want)
    assert dict(rt.telemetry.faults) == {"raise": 2}
    assert dict(rt.telemetry.fallbacks) == {"legacy": 1}
    fb = [ln for ln in launches if ln.fallback == "legacy"]
    assert fb and fb[0].penalty_s == 2 * fb[0].plan.modeled_time_s


def test_reference_rung_is_the_uninjectable_floor():
    # Every non-reference attempt fails (planned, retry, legacy); the
    # sequential per-op reference rung bypasses injection by contract.
    inj = FaultInjector((FaultRule("raise", 1.0),), seed=0)
    rt = _runtime(inj, max_retries=1, quarantine_strikes=10)
    tickets, _ = _serve(rt)
    for tk, want in zip(tickets, _fault_free_results()):
        np.testing.assert_array_equal(np.asarray(tk.result), want)
    assert dict(rt.telemetry.fallbacks) == {"reference": 1}
    assert rt.telemetry.faults["raise"] == 3
    assert rt.telemetry.completed == 3


def test_nan_injection_caught_by_finiteness_guard():
    inj = FaultInjector((FaultRule("nan", 1.0, max_faults=1),), seed=0)
    rt = _runtime(inj, quarantine_strikes=10)
    tickets, _ = _serve(rt)
    assert dict(rt.telemetry.faults) == {"nan": 1}
    assert dict(rt.telemetry.fallbacks) == {"retry": 1}
    for tk in tickets:
        assert bool(jnp.isfinite(tk.result).all())


def test_stall_injection_walks_ladder():
    inj = FaultInjector((FaultRule("stall", 1.0, max_faults=1,
                                   stall_s=1e-3),), seed=0)
    rt = _runtime(inj, quarantine_strikes=10)
    _serve(rt)
    assert dict(rt.telemetry.faults) == {"stall": 1}
    assert dict(rt.telemetry.fallbacks) == {"retry": 1}


# --------------------------------------------------- quarantine (§18.3)
def test_quarantine_fires_with_cache_hygiene_and_probe():
    # Two consecutive injected failures on the planned tile = K strikes:
    # the GO entry is quarantined, its tuned entry dropped, every cached
    # plan using the tile evicted — then the cooldown elapses and
    # process_retunes releases it as a half-open probe.
    inj = FaultInjector((FaultRule("raise", 1.0, max_faults=2),), seed=0)
    rt = _runtime(inj, max_retries=1, quarantine_strikes=2)
    tickets, launches = _serve(rt)
    tele = rt.telemetry
    assert tele.quarantines == 1
    assert tele.quarantine_evictions >= 1   # the flush's own cached plan
    assert rt.ctrl.lib.quarantined()        # tile banned in the library
    assert rt.breaker.quarantined()
    assert dict(tele.fallbacks) == {"legacy": 1}
    for tk, want in zip(tickets, _fault_free_results()):
        np.testing.assert_array_equal(np.asarray(tk.result), want)
    # Half-open probe after the (modeled-timeline) cooldown.
    rt.process_retunes(now=launches[0].start_t + rt.config.quarantine_cooldown_s)
    assert tele.probes == 1
    assert rt.ctrl.lib.quarantined() == {}
    assert not rt.breaker.quarantined()
    assert rt.plan_cache_size == 0          # release invalidated plans


def test_flaky_tile_accumulates_strikes_across_launches():
    # One failure per launch, each completed by retry: `succeed` only
    # resets on PLANNED-rung success, so a tile that is flaky every
    # launch still reaches K strikes and quarantines.
    inj = FaultInjector((FaultRule("raise", 1.0, max_faults=1),), seed=0)
    rt = _runtime(inj, max_retries=2, quarantine_strikes=2)
    rt.submit(_req(D1, 0), now=0.0)
    rt.drain(now=1.0)                       # strike 1, completes via retry
    inj._fired.clear()                      # re-arm: one fault per launch
    rt.submit(_req(D1, 1), now=2.0)
    rt.drain(now=3.0)                       # strike 2 → quarantine
    assert rt.telemetry.quarantines == 1
    assert dict(rt.telemetry.fallbacks) == {"retry": 2}


def test_healthy_planned_launch_resets_breaker():
    inj = FaultInjector((FaultRule("raise", 1.0, max_faults=1),), seed=0)
    rt = _runtime(inj, quarantine_strikes=2)
    rt.submit(_req(D1, 0), now=0.0)
    rt.drain(now=1.0)                       # strike 1 (retry completes)
    rt.submit(_req(D1, 1), now=2.0)
    rt.drain(now=3.0)                       # planned success → reset
    rt.submit(_req(D1, 2), now=4.0)
    rt.drain(now=5.0)
    assert rt.telemetry.quarantines == 0
    assert not rt.breaker.active


# ------------------------------------------------ disabled == unhardened
def test_disabled_injection_is_bitwise_identical():
    plain = _runtime()
    armed = _runtime(FaultInjector((FaultRule("raise", 0.0),), seed=0))
    assert not armed.fault_injector.enabled
    tp, lp = _serve(plain)
    ta, la = _serve(armed)
    for a, b in zip(tp, ta):
        np.testing.assert_array_equal(np.asarray(a.result),
                                      np.asarray(b.result))
        assert a.done_t == b.done_t         # timeline bitwise-identical
    assert plain.device_free_t == armed.device_free_t
    assert all(ln.fallback is None and ln.penalty_s == 0.0 for ln in la)
    assert armed.telemetry.fault_events == 0
    sp, sa = plain.telemetry.summary(), armed.telemetry.summary()
    # class_ratios fold in wall-clock achieved times, host_us and
    # queue_wait_us are host-clock times (non-deterministic across runs);
    # everything modeled, and every count, must match exactly.
    for key in ("class_ratios", "host_us", "queue_wait_us"):
        sp.pop(key), sa.pop(key)
    assert sp == sa


# -------------------------------------------------- calibrator guards
def test_calibrator_ignores_nonfinite_and_nonpositive_times():
    cal = CostCalibrator()
    for bad in (float("inf"), float("nan"), 0.0, -1.0):
        cal.update("gemm", "c", 1e-3, bad)
        cal.update("gemm", "c", bad, 1e-3)
    assert cal.factor("gemm", "c") == 1.0   # no observation folded in
    cal.update("gemm", "c", 1e-3, 2e-3)
    assert cal.factor("gemm", "c") == pytest.approx(2.0)


# ------------------------------------------------------------- property
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000),
       p_raise=st.sampled_from([0.0, 0.3, 0.7]),
       p_nan=st.sampled_from([0.0, 0.4]),
       p_stall=st.sampled_from([0.0, 0.2]))
def test_random_fault_schedules_complete_bitwise_equal(
        seed, p_raise, p_nan, p_stall):
    """§18's end-to-end invariant, property-tested: under ANY seed-keyed
    fault schedule every request completes, results are bitwise-equal to
    the fault-free run, and the telemetry fault counters reconcile 1:1
    with the injector's audit log (each launch here is a single group,
    so every delivered injection is exactly one failed attempt)."""
    reqs = [_req(d, i) for i, d in enumerate([D1, D1, D2, D2, D1, D2])]
    waves = [(0, 2, 0.0), (2, 4, 2.0), (4, 6, 4.0)]   # 3 flushes of 2

    def serve(rt):
        tickets = []
        for lo, hi, now in waves:
            tickets += [rt.submit(r, now=now) for r in reqs[lo:hi]]
            rt.drain(now=now + 1.0)
        return tickets

    base_tk = serve(_runtime())

    inj = FaultInjector((FaultRule("raise", p_raise),
                         FaultRule("nan", p_nan),
                         FaultRule("stall", p_stall, stall_s=1e-4)),
                        seed=seed)
    rt = _runtime(inj, quarantine_strikes=3)
    tickets = serve(rt)

    tele = rt.telemetry
    assert tele.completed == tele.submitted == len(reqs)
    for tk, ref in zip(tickets, base_tk):
        assert tk.done_t is not None
        np.testing.assert_array_equal(np.asarray(tk.result),
                                      np.asarray(ref.result))
    # Audit-log reconciliation: injection is the only failure source.
    assert tele.fault_events == len(inj.log)
    assert "error" not in tele.faults
    assert tele.fallback_events <= tele.fault_events
