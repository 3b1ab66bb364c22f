"""Online serving runtime: admission queues, plan cache, fairness,
telemetry, and the decode-step integration (DESIGN.md §10)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import ConcurrencyController, GemmDesc, GemmRequest, compat_key
from repro.kernels.gemm import gemm_ref
from repro.runtime import (
    DEFAULT_SLO,
    Runtime,
    RuntimeConfig,
    TenantSLO,
    adversarial_trace,
    bursty_trace,
    decode_step_requests,
    poisson_trace,
    submit_decode_step,
)
from tests.hypothesis_compat import given, settings, st

SMALL = GemmDesc(256, 512, 512)
SMALL2 = GemmDesc(1024, 512, 512)      # same compatibility class as SMALL
OTHER = GemmDesc(128, 128, 2048)       # different class


def _runtime(**cfg_kw) -> Runtime:
    # fresh library per runtime so tuned-entry counts are test-isolated
    from repro.core import GOLibrary
    ctrl = ConcurrencyController(library=GOLibrary())
    return Runtime(ctrl, RuntimeConfig(**cfg_kw))


# ----------------------------------------------------------------- queues
def test_submit_routes_to_compatibility_class_queues():
    rt = _runtime()
    rt.submit(SMALL, now=0.0)
    rt.submit(SMALL2, now=0.0)
    rt.submit(OTHER, now=0.0)
    depths = rt.queue_depths()
    assert depths == {compat_key(SMALL): 2, compat_key(OTHER): 1}
    assert rt.pending() == 3


def test_flush_respects_batching_window():
    rt = _runtime(window_s=1.0)
    rt.submit(SMALL, now=0.0)
    assert rt.flush(now=0.5) == []          # window not elapsed
    assert rt.pending() == 1
    launches = rt.flush(now=1.5)
    assert len(launches) == 1 and rt.pending() == 0


def test_drain_force_flushes_everything():
    rt = _runtime(window_s=100.0)
    for _ in range(5):
        rt.submit(SMALL, now=0.0)
    rt.submit(OTHER, now=0.0)
    launches = rt.drain(now=0.0)
    assert rt.pending() == 0
    served = sorted(t.seq for launch in launches for t in launch.tickets)
    assert served == [1, 2, 3, 4, 5, 6]


def test_tickets_carry_latency_and_plan():
    from repro.core import CP_OVERHEAD_S

    rt = _runtime(window_s=0.0)
    tk = rt.submit(SMALL, now=1.0)
    rt.flush(now=2.0)
    assert tk.done_t is not None and tk.plan is not None
    # completion happens on the modeled device timeline, after dispatch;
    # a cold flush (cache miss) pays the CP planning overhead first
    assert tk.latency_s >= 1.0
    assert tk.done_t == pytest.approx(
        2.0 + CP_OVERHEAD_S + tk.plan.modeled_time_s)
    # an identical warm flush skips the planning cost
    tk2 = rt.submit(SMALL, now=10.0)
    rt.flush(now=11.0)
    assert tk2.done_t == pytest.approx(11.0 + tk2.plan.modeled_time_s)


# ------------------------------------------------------------- plan cache
def test_plan_cache_hit_after_identical_flush():
    rt = _runtime(window_s=0.0)

    def one_round(now):
        for _ in range(4):
            rt.submit(SMALL, now=now)
        rt.submit(SMALL2, now=now)
        return rt.flush(now=now + 1.0)

    first = one_round(0.0)
    assert all(not launch.cache_hit for launch in first)
    second = one_round(10.0)
    assert second and all(launch.cache_hit for launch in second)
    # same plans re-bound: identical cd/mode sequence
    assert [(l.plan.cd, l.plan.mode) for l in first] == \
        [(l.plan.cd, l.plan.mode) for l in second]
    assert rt.telemetry.cache_hits >= 1


def test_plan_cache_ignores_arrival_order():
    rt = _runtime(window_s=0.0)
    rt.submit(SMALL, now=0.0)
    rt.submit(SMALL2, now=0.0)
    rt.flush(now=1.0)
    rt.submit(SMALL2, now=2.0)          # reversed arrival order
    rt.submit(SMALL, now=2.0)
    launches = rt.flush(now=3.0)
    assert all(launch.cache_hit for launch in launches)


def test_plan_cache_invalidated_by_availability_change():
    rt = _runtime(window_s=0.0)
    for _ in range(4):
        rt.submit(SMALL, now=0.0)
    assert all(not l.cache_hit for l in rt.flush(now=1.0))
    rt.set_available(2)                 # live parallelism shrank
    for _ in range(4):
        rt.submit(SMALL, now=2.0)
    launches = rt.flush(now=3.0)
    assert all(not launch.cache_hit for launch in launches)
    assert all(launch.plan.cd <= 2 for launch in launches)


def test_plan_cache_lru_eviction():
    rt = _runtime(window_s=0.0, plan_cache_capacity=1)
    rt.submit(SMALL, now=0.0)
    rt.flush(now=1.0)
    rt.submit(OTHER, now=2.0)           # different signature evicts SMALL's
    rt.flush(now=3.0)
    assert rt.plan_cache_size == 1
    rt.submit(SMALL, now=4.0)
    assert all(not launch.cache_hit for launch in rt.flush(now=5.0))


def test_plan_cache_lru_eviction_order_respects_recency():
    """LRU must evict the least-RECENTLY-used signature, not the
    least-recently-inserted one: touching A (a hit) before inserting C
    must keep A and evict B."""
    rt = _runtime(window_s=0.0, plan_cache_capacity=2)

    def one(d, now):
        rt.submit(d, now=now)
        return rt.flush(now=now + 0.1)

    one(SMALL, 0.0)                     # insert A
    one(OTHER, 1.0)                     # insert B
    assert all(l.cache_hit for l in one(SMALL, 2.0))    # touch A (hit)
    one(GemmDesc(64, 64, 4096), 3.0)    # insert C ⇒ evicts B, keeps A
    assert rt.plan_cache_size == 2
    assert all(l.cache_hit for l in one(SMALL, 4.0))    # A retained
    assert all(not l.cache_hit for l in one(OTHER, 5.0))  # B was evicted


def test_plan_cache_hit_accounting_under_adversarial_thrash():
    """Capacity-1 cache with alternating signatures: every flush is a miss
    and the telemetry must say exactly that (no phantom hits), while the
    same sequence at capacity 2 is all hits after warm-up."""
    rt = _runtime(window_s=0.0, plan_cache_capacity=1)
    for r in range(6):
        d = SMALL if r % 2 == 0 else OTHER
        rt.submit(d, now=float(r))
        launches = rt.flush(now=r + 0.5)
        assert all(not l.cache_hit for l in launches)
    assert rt.telemetry.cache_hits == 0
    assert rt.telemetry.cache_misses == 6
    assert rt.telemetry.cache_hit_rate() == 0.0

    rt2 = _runtime(window_s=0.0, plan_cache_capacity=2)
    for r in range(6):
        d = SMALL if r % 2 == 0 else OTHER
        rt2.submit(d, now=float(r))
        launches = rt2.flush(now=r + 0.5)
        assert all(l.cache_hit == (r >= 2) for l in launches)
    assert rt2.telemetry.cache_hits == 4
    assert rt2.telemetry.cache_misses == 2


# ------------------------------------------------------- dispatch fast path
def test_steady_state_flush_zero_evals_zero_resorts():
    """Acceptance: a plan-cache-hit flush performs 0 cost-model
    evaluations and 0 signature re-sorts (DESIGN.md §13)."""
    from repro.core.cost_model import EVAL_COUNTER

    rt = _runtime(window_s=0.0)
    bundle = [SMALL, SMALL, SMALL2, OTHER]
    rt.prewarm(bundle)
    for d in bundle:                     # cold round binds plans
        rt.submit(d, now=0.0)
    rt.flush(now=1.0)
    for r in range(5):
        now = 10.0 + r
        for d in bundle:
            rt.submit(d, now=now)
        e0 = EVAL_COUNTER.evals
        launches = rt.flush(now=now + 0.5)
        assert launches and all(l.cache_hit for l in launches)
        assert EVAL_COUNTER.evals - e0 == 0
        assert rt.telemetry.last_flush_evals == 0
    assert rt.telemetry.flush_sig_resorts == 0
    # ... while prewarm's offline planning DID meter canonical sorts —
    # the sig_resorts counter is live, not dead code
    assert rt.telemetry.sig_resorts > 0
    # and a signature that was never planned DOES evaluate
    rt.submit(GemmDesc(96, 512, 512), now=100.0)
    rt.submit(SMALL, now=100.0)
    miss = rt.flush(now=101.0)
    assert any(not l.cache_hit for l in miss)
    assert rt.telemetry.last_flush_evals > 0
    assert rt.telemetry.flush_evals > 0
    assert rt.telemetry.flush_sig_resorts == 0


def test_incremental_signature_matches_any_arrival_order():
    """The admission-sorted queues must produce one canonical signature
    for every permutation of the same multiset of descs."""
    import itertools

    descs = [SMALL, SMALL2, SMALL, GemmDesc(512, 512, 512)]
    rt = _runtime(window_s=0.0)
    for perm in itertools.permutations(range(len(descs))):
        for i in perm:
            rt.submit(descs[i], now=0.0)
        launches = rt.flush(now=1.0)
        if perm == tuple(range(len(descs))):
            first_plans = [(l.plan.cd, l.plan.mode) for l in launches]
            continue
        assert all(l.cache_hit for l in launches)
        assert [(l.plan.cd, l.plan.mode) for l in launches] == first_plans


def test_set_mesh_invalidates_plans_and_memoized_cds():
    """set_mesh interacts with the incremental signature: pending tickets
    survive, but cached plans AND the controller's memoized CD decisions
    must be dropped so the derated spec re-plans from scratch."""
    from types import SimpleNamespace

    rt = _runtime(window_s=0.0)
    for _ in range(8):
        rt.submit(SMALL, now=0.0)
    rt.flush(now=1.0)
    assert rt.plan_cache_size > 0
    assert rt.ctrl._cd_cache             # memoized decisions exist

    mesh = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 1, "model": 4})
    for _ in range(8):                   # pending tickets across set_mesh
        rt.submit(SMALL, now=2.0)
    res = rt.set_mesh(mesh)
    assert rt.plan_cache_size == 0
    assert not rt.ctrl._cd_cache and not rt.ctrl._feat_cache
    assert rt.available == res.slot_budget < 16
    launches = rt.flush(now=3.0)
    assert launches and all(not l.cache_hit for l in launches)
    assert all(l.plan.cd <= res.slot_budget for l in launches)
    assert rt.telemetry.flush_sig_resorts == 0


# ---------------------------------------------------------------- fairness
def test_round_robin_interleaves_compatibility_classes():
    rt = _runtime(window_s=0.0)
    # tenant "a" floods one class; tenant "b" has a little traffic in another
    for _ in range(12):
        rt.submit(SMALL, tenant="a", now=0.0)
    for _ in range(2):
        rt.submit(OTHER, tenant="b", now=0.0)
    launches = rt.flush(now=1.0)
    classes = [launch.class_key for launch in launches]
    # b's class must be served within the first rotation, not after all of
    # a's groups
    assert compat_key(OTHER) in classes[:2]


def test_round_robin_cursor_rotates_across_flushes():
    rt = _runtime(window_s=0.0)

    def round_(now):
        rt.submit(SMALL, now=now)
        rt.submit(OTHER, now=now)
        return rt.flush(now=now + 1.0)

    first = round_(0.0)[0].class_key
    second = round_(10.0)[0].class_key
    assert first != second              # service starts after last-served


# --------------------------------------------------------------- telemetry
def test_telemetry_counts_and_histogram():
    rt = _runtime(window_s=0.0)
    for _ in range(6):
        rt.submit(SMALL, now=0.0)
    rt.submit(OTHER, now=0.0)
    rt.flush(now=1.0)
    tele = rt.telemetry
    assert tele.submitted == 7 and tele.completed == 7
    assert tele.flushes == 1 and len(tele.groups) >= 2
    hist = tele.queue_depth_histogram()
    assert hist.get("4-7") == 1 and hist.get("1") == 1
    summary = tele.summary()
    assert summary["plan_cache_hit_rate"] == 0.0
    assert summary["modes"]
    # shadow mode (no execution) has no achieved times → no ratios; the
    # snapshot is the summary under its §16 name
    assert summary["class_ratios"] == {}
    assert tele.snapshot() == summary


def test_telemetry_host_counters_count_every_submit_and_flush():
    rt = _runtime(window_s=0.0)
    for _ in range(3):
        rt.submit(SMALL, now=0.0)
    rt.submit([SMALL, OTHER], now=0.0)      # a bundle is one submit
    rt.flush(now=1.0)
    rt.flush(now=2.0)                       # nothing ripe: planning only
    tele = rt.telemetry
    assert tele.host_calls == {"submit": 4, "plan": 2, "launch": 1, "record": 1}
    assert all(tele.host_s[k] > 0 for k in tele.host_calls)
    assert tele.queue_waits == 5            # three ops and the bundle's two
    assert 0 < tele.queue_wait_max_s <= tele.queue_wait_s
    summary = tele.summary()
    assert set(summary["host_us"]) == {"submit", "plan", "launch", "record"}
    assert summary["host_us"]["plan"] == pytest.approx(
        1e6 * tele.host_s["plan"] / 2, abs=1e-3)
    assert summary["host_calls"] == dict(tele.host_calls)
    wait = summary["queue_wait_us"]
    assert 0 < wait["mean"] <= wait["max"]


def test_virtual_clock_replay_keeps_its_modeled_telemetry():
    """A replay on a virtual clock, with EDF, admission slicing and
    deferrals, reads the modeled numbers the runtime gave before its
    host-clock counters existed: those run beside the timeline, never on
    it."""
    from repro.core import GOLibrary

    clock = {"t": 0.0}
    rt = Runtime(ConcurrencyController(library=GOLibrary()),
                 RuntimeConfig(policy="edf", slicing=True, flush_budget_s=3e-4),
                 clock=lambda: clock["t"])
    rt.set_tenant_slo("chat", TenantSLO("latency", 2.0, 5e-3))
    descs = {"chat": [GemmDesc(8, 4096, 4096), GemmDesc(8, 11008, 4096)],
             "batch": [GemmDesc(4096, 8192, 8192), GemmDesc(512, 1024, 4096)]}
    arrivals = sorted([(t, "chat") for t in poisson_trace(400.0, 0.1, seed=3)]
                      + [(t, "batch") for t in poisson_trace(60.0, 0.1, seed=4)])
    tickets = []
    for i, (t, tenant) in enumerate(arrivals):
        clock["t"] = t
        tickets.append(rt.submit(descs[tenant][i % 2], tenant=tenant))
        rt.flush()
    clock["t"] = 0.2
    rt.drain()
    s = rt.telemetry.summary()
    assert {k: s[k] for k in ("submitted", "completed", "flushes", "groups", "max_cd",
                              "modes", "sliced_ops", "deferred_launches")} == {
        "submitted": 45, "completed": 45, "flushes": 45, "groups": 53, "max_cd": 3,
        "modes": {"grouped": 5, "single": 48}, "sliced_ops": 2, "deferred_launches": 219}
    assert s["mean_cd"] == 1.113 and s["plan_cache_hit_rate"] == 0.6721
    assert s["modeled_busy_time_us"] == pytest.approx(9214.96, abs=1e-6)
    assert s["tenants"] == {
        "batch": {"n": 2, "p50_ms": 132.9104, "p95_ms": 137.2162, "p99_ms": 137.2162},
        "chat": {"n": 43, "p50_ms": 3.1244, "p95_ms": 9.6212, "p99_ms": 103.059}}
    assert rt.device_free_t == pytest.approx(0.20340411054637242, rel=1e-12)
    assert sum(t.latency_s for t in tickets) == pytest.approx(0.6018931006914715, rel=1e-12)
    assert rt.telemetry.host_calls["submit"] == 45


def test_prewarm_tunes_and_seeds_plan_cache():
    rt = _runtime(window_s=0.0)
    fresh = rt.prewarm([SMALL, SMALL, OTHER])
    assert fresh == 2                   # deduplicated by desc key
    assert rt.plan_cache_size >= 2
    assert rt.prewarm([SMALL]) == 0     # already tuned


# ----------------------------------------------------------------- execute
def test_execute_grouped_launches_match_reference():
    rt = _runtime(window_s=0.0, execute=True, interpret=True)
    key = jax.random.PRNGKey(0)
    d = GemmDesc(128, 192, 128, dtype="f32")
    tickets = []
    for i in range(4):
        a = jax.random.normal(jax.random.fold_in(key, i), (d.M, d.K))
        b = jax.random.normal(jax.random.fold_in(key, 100 + i), (d.K, d.N))
        tickets.append(rt.submit(GemmRequest(desc=d, a=a, b=b), now=0.0))
    rt.drain(now=1.0)
    for tk in tickets:
        np.testing.assert_allclose(
            tk.result, gemm_ref(tk.request.a, tk.request.b),
            rtol=3e-4, atol=3e-4,
        )
    assert any(g.achieved_time_s is not None for g in rt.telemetry.groups)
    # executed launches feed per-class modeled-vs-achieved ratios (§16)
    ratios = rt.telemetry.class_ratios()
    assert ratios[compat_key(d)]["n"] >= 1
    assert ratios[compat_key(d)]["geomean_ratio"] > 0
    assert ratios[compat_key(d)]["mean_abs_log"] >= 0
    assert rt.telemetry.summary()["class_ratios"] == ratios


# -------------------------------------------------------------- integration
def test_decode_step_requests_apply_fusion_policy():
    ctrl = ConcurrencyController()
    cfg = get_arch("stablelm-3b")
    raw = decode_step_requests(ctrl, cfg, batch=8, fuse_policy=False)
    fused = decode_step_requests(ctrl, cfg, batch=8, fuse_policy=True)
    # raw stream has q, k, v separately; the policy stream decided §6.11
    assert sum(r.tag == "qkv" for r in raw) == 3
    qkv_fused = [r for r in fused if r.tag.startswith("qkv")]
    if len(qkv_fused) == 1:             # fuse chosen
        assert qkv_fused[0].tag == "qkv-fused"
        assert qkv_fused[0].desc.N == sum(
            r.desc.N for r in raw if r.tag == "qkv")
    else:                               # group chosen
        assert len(qkv_fused) == 3
    # total FLOPs are preserved either way
    assert sum(r.desc.flops for r in fused if r.tag.startswith("qkv")) == \
        sum(r.desc.flops for r in raw if r.tag == "qkv")


def test_submit_decode_step_routes_moe_experts():
    rt = _runtime(window_s=0.0)
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    tickets = submit_decode_step(rt, cfg, batch=4, tenant="moe", now=0.0)
    assert len(tickets) > cfg.moe_top_k     # experts dominate the bundle
    launches = rt.flush(now=1.0)
    # independent per-expert GEMMs group concurrently
    assert any(launch.plan.cd > 1 for launch in launches)


# ------------------------------------------------ multi-tenant SLOs (§17)
BIG = GemmDesc(8192, 512, 512)          # same compat class as SMALL, huge M


def test_admission_slices_oversized_ops():
    """Slicing on + tiny budget: an oversized op enters the queues only
    as pieces; the parent ticket is what the caller holds."""
    rt = _runtime(window_s=0.0, slicing=True, flush_budget_s=10.0,
                  slice_budget_frac=1e-9)      # threshold → everything slices
    tk = rt.submit(BIG, now=0.0)
    assert tk.sliced and len(tk.pieces) == rt.config.max_slices
    assert rt.pending() == rt.config.max_slices   # pieces, not the parent
    assert sum(p.desc.M for p in tk.pieces) == BIG.M
    assert all(compat_key(p.desc) == compat_key(BIG) for p in tk.pieces)
    assert rt.telemetry.sliced_ops == 1
    assert rt.telemetry.slice_counts["default"] == rt.config.max_slices
    rt.drain(now=1.0)
    # parent completes with its last piece, on the modeled timeline
    assert tk.done_t == max(p.done_t for p in tk.pieces)
    assert rt.telemetry.completed == 1    # parents count once, pieces don't


def test_admission_leaves_small_ops_whole():
    rt = _runtime(window_s=0.0, slicing=True, flush_budget_s=10.0)
    tk = rt.submit(GemmDesc(8, 128, 128), now=0.0)
    assert not tk.sliced and rt.pending() == 1
    # slicing off entirely → even BIG stays whole
    rt2 = _runtime(window_s=0.0)
    assert not rt2.submit(BIG, now=0.0).sliced


def test_sliced_execution_merges_parent_result():
    rt = _runtime(window_s=0.0, execute=True, interpret=True, slicing=True,
                  flush_budget_s=10.0, slice_budget_frac=1e-9)
    key = jax.random.PRNGKey(1)
    d = GemmDesc(128, 192, 128, dtype="f32")
    a = jax.random.normal(jax.random.fold_in(key, 0), (d.M, d.K))
    b = jax.random.normal(jax.random.fold_in(key, 1), (d.K, d.N))
    tk = rt.submit(GemmRequest(desc=d, a=a, b=b), now=0.0)
    assert tk.sliced
    rt.drain(now=1.0)
    assert tk.result is not None and tk.result.shape == (d.M, d.N)
    np.testing.assert_allclose(tk.result, gemm_ref(a, b),
                               rtol=3e-4, atol=3e-4)


def test_edf_flush_serves_earliest_deadline_first():
    rt = _runtime(window_s=0.0, policy="edf")
    rt.set_tenant_slo("lat", TenantSLO("latency", weight=4.0,
                                       p99_target_s=1e-3))
    # batch tenant floods first; latency tenant arrives after
    for _ in range(6):
        rt.submit(OTHER, tenant="batch", now=0.0)
    lat_tk = rt.submit(SMALL, tenant="lat", now=0.0)
    launches = rt.flush(now=1.0)
    assert lat_tk in launches[0].tickets  # earliest deadline goes first
    deadlines = [min(t.deadline_t for t in ln.tickets) for ln in launches]
    assert deadlines == sorted(deadlines)


def test_edf_deadlines_are_absolute_no_starvation():
    """A waiting ticket's deadline never moves, so fresh arrivals with
    the same SLO always sort behind it (bounded wait)."""
    rt = _runtime(window_s=0.0, policy="edf", flush_budget_s=1e-7)
    old = rt.submit(SMALL, now=0.0)
    rt.flush(now=1.0)                     # budget defers nothing ripe yet?
    fresh = rt.submit(SMALL, now=2.0)
    assert old.deadline_t < fresh.deadline_t
    rt.drain(now=3.0)
    assert old.done_t is not None and fresh.done_t is not None
    assert old.done_t <= fresh.done_t


def test_budgeted_flush_defers_and_drain_terminates():
    rt = _runtime(window_s=0.0, policy="edf", flush_budget_s=1e-9)
    for _ in range(5):
        rt.submit(SMALL, now=0.0)
    for _ in range(5):
        rt.submit(OTHER, now=0.0)
    first = rt.flush(now=1.0)
    # horizon is tiny: at least one launch binds, the rest requeue
    assert len(first) >= 1
    assert rt.pending() > 0 or rt.telemetry.deferred_launches == 0
    rest = rt.drain(now=1.0)
    assert rt.pending() == 0
    assert rt.telemetry.deferred_launches > 0
    assert rt.telemetry.completed == 10
    # deferral preserved deadlines → overall completion order still EDF-ish
    assert all(ln.start_t is not None for ln in first + rest)


def test_sliced_plan_cache_signature_stable_steady_state():
    """Pieces are ordinary descs with canonical keys: a sliced workload
    reaches the same zero-eval steady state as a whole one (§17.2)."""
    from repro.core.cost_model import EVAL_COUNTER

    rt = _runtime(window_s=0.0, slicing=True, flush_budget_s=10.0,
                  slice_budget_frac=1e-9)
    rt.submit(BIG, now=0.0)               # cold round binds piece plans
    rt.flush(now=1.0)
    for r in range(4):
        now = 10.0 + r
        rt.submit(BIG, now=now)
        e0 = EVAL_COUNTER.evals
        launches = rt.flush(now=now + 0.5)
        assert launches and all(l.cache_hit for l in launches)
        assert EVAL_COUNTER.evals - e0 == 0
        assert rt.telemetry.last_flush_evals == 0
    assert rt.telemetry.flush_sig_resorts == 0


def test_edf_mixed_bundle_ranks_join_signature():
    """Non-uniform ranks in the mixed queue change the plan, so they
    join the signature — and static tenant ranks still steady-state."""
    rt = _runtime(window_s=0.0, policy="edf")
    rt.set_tenant_slo("lat", TenantSLO("latency", weight=2.0,
                                       p99_target_s=1e-3))
    bundle_a = [SMALL, OTHER]
    bundle_b = [SMALL2]

    def round_(now):
        rt.submit_bundle(bundle_a, tenant="batch", now=now)
        rt.submit_bundle(bundle_b, tenant="lat", now=now)
        return rt.flush(now=now + 0.5)

    first = round_(0.0)
    assert all(not ln.cache_hit for ln in first)
    second = round_(10.0)
    assert second and all(ln.cache_hit for ln in second)
    assert [(ln.plan.cd, ln.plan.mode) for ln in first] == \
        [(ln.plan.cd, ln.plan.mode) for ln in second]
    # rank-0 members land in the earliest chunk of the mixed plan
    ranked = [min(t.rank for t in ln.tickets) for ln in first]
    assert ranked[0] == 0


def test_set_mesh_composes_with_sliced_queues():
    """set_mesh must clear the admission estimate cache too — the spec
    changed, so slicing decisions re-derive — while pending sliced
    pieces survive and still merge their parent."""
    from types import SimpleNamespace

    rt = _runtime(window_s=0.0, slicing=True, flush_budget_s=10.0,
                  slice_budget_frac=1e-9)
    tk = rt.submit(BIG, now=0.0)
    assert tk.sliced and rt._iso_cache
    mesh = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 1, "model": 4})
    rt.set_mesh(mesh)
    assert rt._iso_cache == {}            # estimates follow the spec
    assert rt.plan_cache_size == 0
    rt.drain(now=1.0)
    assert tk.done_t is not None
    assert all(p.done_t is not None for p in tk.pieces)


def test_tenant_slo_registry_and_defaults():
    rt = _runtime()
    assert rt.tenant_slo("nobody") is DEFAULT_SLO
    assert DEFAULT_SLO.rank == 1
    slo = TenantSLO("latency", weight=3.0, p99_target_s=2e-3)
    assert slo.rank == 0
    rt.set_tenant_slo("a", slo)
    assert rt.tenant_slo("a") is slo
    tk = rt.submit(SMALL, tenant="a", now=5.0)
    assert tk.deadline_t == pytest.approx(5.0 + 2e-3)
    assert tk.rank == 0


def test_tenant_percentiles_nearest_rank():
    rt = _runtime()
    for i in range(1, 101):
        rt.telemetry.record_latency("t", i * 1e-3)
    pct = rt.telemetry.tenant_percentiles()["t"]
    assert pct["n"] == 100
    assert pct["p50_ms"] == pytest.approx(50.0)
    assert pct["p95_ms"] == pytest.approx(95.0)
    assert pct["p99_ms"] == pytest.approx(99.0)
    summary = rt.telemetry.summary()
    assert summary["tenants"]["t"] == pct
    assert "slice_counts" in summary and "deferred_launches" in summary


@given(st.lists(st.tuples(st.sampled_from(["lat", "batch"]),
                          st.sampled_from([0, 1, 2]),
                          st.floats(0.0, 1e-3)),
                min_size=1, max_size=12))
@settings(max_examples=20, deadline=None)
def test_edf_random_traces_complete_and_order_by_deadline(events):
    """Property: under EDF + a flush budget, every submission (and every
    sliced parent) completes — drain always terminates — and the modeled
    device timeline is monotone across the deferral/requeue churn."""
    descs = [SMALL, OTHER, BIG]
    rt = _runtime(window_s=0.0, policy="edf", slicing=True,
                  flush_budget_s=1e-4, slice_budget_frac=0.5)
    rt.set_tenant_slo("lat", TenantSLO("latency", weight=4.0,
                                       p99_target_s=1e-3))
    tickets = [rt.submit(descs[di], tenant=tn, now=t)
               for tn, di, t in sorted(events, key=lambda e: e[2])]
    launches = rt.drain(now=1e-3)
    assert all(tk.done_t is not None for tk in tickets)
    for tk in tickets:
        if tk.sliced:
            assert all(p.done_t is not None for p in tk.pieces)
    starts = [ln.start_t for ln in launches]
    assert starts == sorted(starts)


# ------------------------------------------------------------------ traces
def test_traces_deterministic_sorted_and_bounded():
    a = poisson_trace(1000.0, 0.1, seed=3)
    b = poisson_trace(1000.0, 0.1, seed=3)
    assert a == b and a == sorted(a)
    assert all(0 < t < 0.1 for t in a)
    assert 50 < len(a) < 200                # ~100 expected
    burst = bursty_trace(1000.0, 0.5, seed=4)
    assert burst == sorted(burst)
    assert all(0 < t < 0.5 for t in burst)


def test_adversarial_trace_deterministic_and_independent():
    a = adversarial_trace(3, 500.0, 0.1, 200.0, seed=5)
    b = adversarial_trace(3, 500.0, 0.1, 200.0, seed=5)
    assert a == b and a == sorted(a, key=lambda e: (e[0], e[1]))
    tenants = {tn for _, tn in a}
    assert tenants == {"abuse", "lat0", "lat1", "lat2"}
    assert all(0 < t < 0.1 for t, _ in a)
    # per-tenant streams are independent: adding a tenant never perturbs
    # the existing tenants' arrivals
    wider = adversarial_trace(4, 500.0, 0.1, 200.0, seed=5)
    for tn in tenants:
        assert [t for t, x in a if x == tn] == \
            [t for t, x in wider if x == tn]
    with pytest.raises(ValueError):
        adversarial_trace(0, 500.0, 0.1, 200.0)
