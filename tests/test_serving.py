"""Serving layer: admission control, budget-aware ladder walks,
per-rung circuit breakers, versioned caching, and graceful degradation
under load (ISSUE 9).

Layout:
  - budget-aware ``ResiliencePolicy.execute`` extensions (Deadline,
    rung_gate, on_rung, wall_s/slack accounting, report-on-raise)
  - serve primitives: AdmissionController / CircuitBreaker / ResultCache
  - ButterflyService: parity vs the one-shot engines, cache tiers,
    deadline degradation, stale fallback, breaker trips
  - the concurrency stress suite (mixed query mix == serial, no
    cache poisoning); its fault cells (overload shed, slow_rung
    degradation) run under ``REPRO_FAULTS=1``

Everything runs on deliberately tiny graphs: the suite exercises
control flow, not throughput — the closed-loop latency story lives in
``benchmarks/bench_serving.py``.
"""
import concurrent.futures as cf
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import BipartiteGraph, count_butterflies
from repro.core.approx import ApproxCount
from repro.core.peel import peel_tips, peel_tips_stored, peel_wings
from repro.core import resilience as res
from repro.data.graphs import powerlaw_bipartite
from repro.serve import (
    AdmissionController,
    AdmissionRejected,
    ButterflyService,
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    Query,
    ResultCache,
)
from repro.serve.service import default_count_engine
from repro.testing import faults

RUN_FAULTS = os.environ.get("REPRO_FAULTS") == "1"
needs_faults = pytest.mark.skipif(
    not RUN_FAULTS, reason="chaos cells run under REPRO_FAULTS=1"
)

G1 = powerlaw_bipartite(80, 60, 400, seed=1)
G2 = powerlaw_bipartite(70, 90, 350, seed=2)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# Deadline + budget-aware execute()
# ---------------------------------------------------------------------------


def test_deadline_countdown_and_expiry():
    clk = FakeClock()
    d = Deadline(2.0, clock=clk)
    assert d.remaining_s() == 2.0 and not d.expired()
    clk.advance(1.5)
    assert abs(d.remaining_s() - 0.5) < 1e-9
    clk.advance(1.0)
    assert d.expired()
    err = d.exceeded("late")
    assert isinstance(err, DeadlineExceeded)
    assert err.deadline_s == 2.0 and err.elapsed_s == 2.5
    with pytest.raises(ValueError):
        Deadline(0.0)


def test_execute_records_wall_and_slack():
    clk = FakeClock()
    pol = res.ResiliencePolicy(clock=clk)

    def run(shrinks):
        clk.advance(0.25)
        return 42

    out, rep = pol.execute(
        "w", [res.Rung("r", run)], deadline=Deadline(1.0, clock=clk)
    )
    assert out == 42
    assert rep.attempts[0].wall_s == 0.25
    assert rep.wall_s == 0.25
    assert rep.deadline_s == 1.0
    assert abs(rep.deadline_slack_s - 0.75) < 1e-9
    s = rep.summary()
    assert "wall=0.250s" in s and "slack=0.750s" in s


def test_execute_deadline_skips_then_raises_typed():
    clk = FakeClock()
    pol = res.ResiliencePolicy(clock=clk, backoff_base_s=0.0)
    d = Deadline(1.0, clock=clk)

    def slow(shrinks):
        clk.advance(2.0)  # burns the whole budget
        raise res.CapacityOverflow("tile bound")

    calls = []

    def never(shrinks):
        calls.append(1)
        return 1

    with pytest.raises(DeadlineExceeded) as ei:
        pol.execute(
            "w", [res.Rung("a", slow), res.Rung("b", never)], deadline=d
        )
    assert not calls, "expired budget must not start another rung"
    rep = ei.value.report  # raised errors carry the audit trail
    assert [a.outcome for a in rep.attempts] == [
        "capacity-overflow", "deadline-skipped"
    ]


def test_execute_zero_cost_rung_survives_expiry():
    clk = FakeClock()
    pol = res.ResiliencePolicy(clock=clk)
    d = Deadline(0.5, clock=clk)
    clk.advance(1.0)  # already expired
    out, rep = pol.execute(
        "w", [res.Rung("cache", lambda s: "hit", zero_cost=True)],
        deadline=d,
    )
    assert out == "hit"
    assert rep.final_rung == "cache"


def test_execute_rung_gate_and_on_rung_hooks():
    pol = res.ResiliencePolicy()
    seen = []

    def gate(rung):
        return "vetoed" if rung.name == "a" else None

    out, rep = pol.execute(
        "w",
        [res.Rung("a", lambda s: 1), res.Rung("b", lambda s: 2)],
        rung_gate=gate, on_rung=lambda a: seen.append(a.outcome),
    )
    assert out == 2
    assert [a.outcome for a in rep.attempts] == ["skipped", "ok"]
    assert seen == ["skipped", "ok"]
    assert rep.attempts[0].detail == "vetoed"
    # every rung gated -> typed RungUnavailable, not an opaque crash
    with pytest.raises(res.RungUnavailable):
        pol.execute(
            "w", [res.Rung("a", lambda s: 1)], rung_gate=lambda r: "no"
        )


def test_execute_deadline_exceeded_from_rung_descends():
    """A rung raising DeadlineExceeded mid-flight (supervisor budget)
    descends to cheaper rungs instead of aborting the walk."""
    pol = res.ResiliencePolicy()

    def slow(shrinks):
        raise DeadlineExceeded("round budget gone", deadline_s=1.0)

    out, rep = pol.execute(
        "w", [res.Rung("dist", slow), res.Rung("host", lambda s: 7)]
    )
    assert out == 7
    assert [a.outcome for a in rep.attempts] == [
        "deadline-exceeded", "ok"
    ]


def test_execute_device_lost_recorded_and_report_attached():
    pol = res.ResiliencePolicy()

    def die(shrinks):
        raise res.DeviceLost("gone", device=3)

    with pytest.raises(res.DeviceLost) as ei:
        pol.execute("w", [res.Rung("dev", die)])
    rep = ei.value.report
    assert rep.attempts[-1].outcome == "device-lost"
    assert rep.final_rung is None


def test_execute_backoff_clamped_to_budget():
    clk = FakeClock()
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clk.advance(s)

    pol = res.ResiliencePolicy(
        clock=clk, sleep=sleep, backoff_base_s=10.0, max_retries=2
    )
    d = Deadline(1.0, clock=clk)

    def oom(shrinks):
        clk.advance(0.1)
        raise res.ResourceExhausted("RESOURCE_EXHAUSTED")

    # the 10s backoff must be clamped to the 0.9s remaining budget, and
    # the expired budget stops further retries (the rung's own error
    # surfaces — nothing was deadline-*skipped*, so it isn't masked)
    with pytest.raises(res.ResourceExhausted) as ei:
        pol.execute("w", [res.Rung("r", oom)], deadline=d)
    assert sleeps and all(s <= 1.0 for s in sleeps), sleeps
    assert ei.value.report.attempts[0].retries == 1


# ---------------------------------------------------------------------------
# Serve primitives
# ---------------------------------------------------------------------------


def test_admission_controller_sheds_typed():
    adm = AdmissionController(2)
    adm.try_admit()
    adm.try_admit()
    with pytest.raises(AdmissionRejected) as ei:
        adm.try_admit()
    assert ei.value.queue_depth == 2 and ei.value.capacity == 2
    assert isinstance(ei.value, res.ResilienceError)
    adm.release()
    adm.try_admit()  # freed slot readmits
    s = adm.stats()
    assert s["rejected"] == 1 and s["admitted"] == 3
    assert s["peak_occupancy"] == 2
    with pytest.raises(ValueError):
        AdmissionController(0)


def test_circuit_breaker_state_machine():
    clk = FakeClock()
    br = CircuitBreaker(threshold=2, cooldown_s=5.0, clock=clk)
    assert br.state == "closed" and br.allow() is None
    br.record_failure()
    assert br.state == "closed"  # below threshold
    br.record_failure()
    assert br.state == "open" and br.trips == 1
    assert "breaker open" in br.allow()
    clk.advance(5.0)
    assert br.state == "half-open"
    assert br.allow() is None  # the single probe
    assert "probe already in flight" in br.allow()  # concurrent veto
    br.record_failure()  # probe failed -> reopen, fresh cooldown
    assert br.state == "open" and br.trips == 2
    clk.advance(5.0)
    assert br.allow() is None
    br.record_success()  # probe ok -> closed, counters reset
    assert br.state == "closed" and br.allow() is None
    assert br.snapshot()["consecutive_failures"] == 0


def test_circuit_breaker_neutral_frees_probe():
    clk = FakeClock()
    br = CircuitBreaker(threshold=1, cooldown_s=1.0, clock=clk)
    br.record_failure()
    clk.advance(1.0)
    assert br.allow() is None  # probe taken
    br.record_neutral()  # probe never reported health (e.g. gated off)
    assert br.allow() is None  # slot is free again, not wedged


def test_result_cache_versioned_and_stale():
    c = ResultCache()
    assert c.get("v1", "q") is None
    c.put("v1", "g", "q", "r1")
    assert c.get("v1", "q") == "r1"
    assert c.get("v2", "q") is None  # version miss
    assert c.invalidate_version("v1") == 1
    assert c.get("v1", "q") is None
    assert c.stale_get("g", "q") == ("v1", "r1")  # survives invalidation
    assert c.stale_get("g", "other") is None
    s = c.stats()
    assert s["hits"] == 1 and s["stale_hits"] == 1


# ---------------------------------------------------------------------------
# ButterflyService
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def svc():
    service = ButterflyService(workers=2, queue_cap=4)
    service.register("g1", G1)
    service.register("g2", G2)
    yield service
    service.close()


def test_register_idempotent_and_versioned(svc):
    v1 = svc.registered()["g1"]
    assert svc.register("g1", G1) == v1  # same content: no-op
    assert v1 == G1.content_hash()
    assert svc.registered()["g2"] != v1
    with pytest.raises(KeyError, match="not registered"):
        svc.query(Query(graph="nope"))


def test_count_query_parity_all_modes(svc):
    for mode in ("global", "vertex", "edge", "all"):
        r = svc.query(Query(graph="g1", kind="count", mode=mode))
        ref = count_butterflies(G1, mode=mode, engine="fused")
        if mode in ("global", "all"):
            assert int(r.result.total) == int(ref.total)
        if mode in ("vertex", "all"):
            assert np.array_equal(r.result.per_u, ref.per_u)
            assert np.array_equal(r.result.per_v, ref.per_v)
        if mode in ("edge", "all"):
            assert np.array_equal(r.result.per_edge, ref.per_edge)
        assert r.service.cache == "miss"
        assert r.execution.final_rung == "fused"


def test_count_query_beyond_int32_is_exact():
    """Under x64 the service counts in int64: the complete bipartite
    graph K(2, 70000) has C(70000, 2) butterflies, beyond int32. (x64
    is set globally: ``jax.enable_x64`` is thread-local, and the
    service counts on its worker threads.)"""
    b = 70_000
    g = BipartiteGraph(2, b, np.stack(
        [np.repeat(np.arange(2), b), np.tile(np.arange(b), 2)], axis=1))
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        with ButterflyService(workers=1) as service:
            service.register("wide", g)
            r = service.query(Query(graph="wide"))
    finally:
        jax.config.update("jax_enable_x64", x64)
    assert int(r.result.total) == b * (b - 1) // 2
    assert int(r.result.total) > np.iinfo(np.int32).max


def test_default_count_engine_follows_backend(monkeypatch):
    """fused off the TPU (interpret-mode kernels are slow); fused_pallas
    on it, where the sort-based engines compile for minutes."""
    assert default_count_engine() == "fused"
    assert Query(graph="g").resolved_engine() == "fused"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_count_engine() == "fused_pallas"
    assert Query(graph="g").resolved_engine() == "fused_pallas"
    assert Query(graph="g", kind="peel_tips").resolved_engine() == "host"


def test_peel_query_parity_all_kinds(svc):
    refs = {
        "peel_tips": peel_tips(G2),
        "peel_tips_stored": peel_tips_stored(G2),
        "peel_wings": peel_wings(G2),
    }
    for kind, ref in refs.items():
        r = svc.query(Query(graph="g2", kind=kind))
        assert np.array_equal(r.result.numbers, ref.numbers), kind
        assert r.result.side == ref.side
        assert r.result.rounds == ref.rounds
        assert r.service.final_rung == "host/exact"


def test_cache_hit_is_exact_and_reported(svc):
    q = Query(graph="g1", kind="count", mode="global")
    first = svc.query(q)
    hit = svc.query(q)
    assert hit.service.cache == "hit"
    assert hit.execution is None  # nothing executed
    assert int(hit.result.total) == int(first.result.total)
    assert svc.cache.stats()["hits"] >= 1


def test_reregistration_invalidates_exact_cache():
    service = ButterflyService(workers=1, queue_cap=2)
    service.register("g", G1)
    try:
        q = Query(graph="g", kind="count", mode="global")
        r1 = service.query(q)
        assert service.query(q).service.cache == "hit"
        service.register("g", G2)  # new content, new version
        r2 = service.query(q)
        assert r2.service.cache == "miss"  # old version's entry is gone
        ref = count_butterflies(G2, mode="global", engine="fused")
        assert int(r2.result.total) == int(ref.total)
        assert int(r2.result.total) != int(r1.result.total)
    finally:
        service.close()


def test_bad_queries_are_typed(svc):
    with pytest.raises(ValueError, match="kind"):
        svc.query(Query(graph="g1", kind="frobnicate"))
    with pytest.raises(ValueError, match="mode"):
        svc.query(Query(graph="g1", kind="count", mode="nope"))
    with pytest.raises(ValueError, match="engine"):
        svc.query(Query(graph="g1", kind="count", engine="cuda"))
    with pytest.raises(ValueError, match="deadline_s"):
        svc.query(Query(graph="g1", deadline_s=-1.0))


def test_deadline_degradation_is_bitwise_identical(svc):
    """A warm cost model + tight budget skips the expensive rung; the
    degraded answer is bitwise-identical to the skipped rung's."""
    warm = svc.query(Query(graph="g1", kind="count", mode="vertex"))
    version = svc.registered()["g1"]
    est = svc._estimate_s(version, "fused")
    assert est is not None and est > 0
    # a budget below the learned fused cost but generous for xla
    tight = Query(graph="g1", kind="count", mode="vertex",
                  deadline_s=max(est * 0.5, 0.05))
    # drop the cached entry so execution actually happens
    svc.cache.invalidate_version(version)
    r = svc.query(tight)
    if r.service.degraded:  # xla fit the budget
        assert r.service.final_rung == "xla"
        assert any("skipped" in s for s in r.service.rungs_tried)
        assert np.array_equal(r.result.per_u, warm.result.per_u)
        assert np.array_equal(r.result.per_v, warm.result.per_v)


def test_stale_fallback_marked_and_typed_without_it(svc):
    """When no live rung fits the budget, allow_stale serves the last
    good result explicitly marked; allow_stale=False raises typed."""
    q = Query(graph="g1", kind="count", mode="edge")
    good = svc.query(q)  # seeds the stale store
    version = svc.registered()["g1"]
    svc.cache.invalidate_version(version)  # force real execution
    starved = Query(graph="g1", kind="count", mode="edge",
                    deadline_s=1e-6)
    r = svc.query(starved)
    assert r.service.cache == "stale"
    assert r.service.stale_version == version
    assert np.array_equal(r.result.per_edge, good.result.per_edge)
    svc.cache.invalidate_version(version)
    with pytest.raises(res.ResilienceError):
        svc.query(Query(graph="g1", kind="count", mode="edge",
                        deadline_s=1e-6, allow_stale=False))


def test_breaker_opens_on_repeated_oom_and_recovers():
    clkless = ButterflyService(
        workers=1, queue_cap=2, breaker_threshold=2,
        breaker_cooldown_s=0.05,
    )
    clkless.register("g", G1)
    version = clkless.registered()["g"]
    q = Query(graph="g", kind="count", mode="global", engine="xla",
              allow_stale=False)
    try:
        with faults.inject("oom", site="count.xla"):
            for _ in range(2):
                with pytest.raises(res.ResilienceError):
                    clkless.query(q)
        snap = clkless.breaker_snapshot(version)["xla"]
        assert snap["state"] == "open" and snap["trips"] == 1
        # while open: the only rung is gated -> typed RungUnavailable
        with pytest.raises(res.RungUnavailable):
            clkless.query(q)
        # after the cooldown the half-open probe runs clean and closes
        import time as _t
        _t.sleep(0.06)
        r = clkless.query(q)
        ref = count_butterflies(G1, mode="global", engine="xla")
        assert int(r.result.total) == int(ref.total)
        assert clkless.breaker_snapshot(version)["xla"]["state"] == "closed"
    finally:
        clkless.close()


def test_admission_shed_is_synchronous_and_typed():
    service = ButterflyService(workers=1, queue_cap=0)
    service.register("g", G1)
    gate = threading.Event()
    release = threading.Event()

    orig = service._run

    def slow_run(*a, **kw):
        gate.set()
        release.wait(5.0)
        return orig(*a, **kw)

    service._run = slow_run
    try:
        fut = service.submit(Query(graph="g", kind="count"))
        assert gate.wait(5.0)
        with pytest.raises(AdmissionRejected) as ei:
            service.submit(Query(graph="g", kind="count"))
        assert ei.value.capacity == 1
        release.set()
        fut.result(timeout=30)
        assert service.stats()["shed"] == 1
    finally:
        release.set()
        service.close()


# ---------------------------------------------------------------------------
# Concurrency stress suite (satellite 4)
# ---------------------------------------------------------------------------

MIX = [
    Query(graph="g1", kind="count", mode="global"),
    Query(graph="g1", kind="count", mode="vertex"),
    Query(graph="g2", kind="count", mode="edge"),
    Query(graph="g1", kind="peel_tips"),
    Query(graph="g2", kind="peel_tips_stored"),
    Query(graph="g2", kind="peel_wings"),
]


def _serial_oracle():
    return {
        ("g1", "count", "global"): count_butterflies(
            G1, mode="global", engine="fused"),
        ("g1", "count", "vertex"): count_butterflies(
            G1, mode="vertex", engine="fused"),
        ("g2", "count", "edge"): count_butterflies(
            G2, mode="edge", engine="fused"),
        ("g1", "peel_tips", None): peel_tips(G1),
        ("g2", "peel_tips_stored", None): peel_tips_stored(G2),
        ("g2", "peel_wings", None): peel_wings(G2),
    }


def _check_against_oracle(q: Query, result, oracle) -> None:
    key = (q.graph, q.kind,
           q.mode if q.kind == "count" else None)
    ref = oracle[key]
    if q.kind == "count":
        if q.mode == "global":
            assert int(result.total) == int(ref.total)
        elif q.mode == "vertex":
            assert np.array_equal(result.per_u, ref.per_u)
            assert np.array_equal(result.per_v, ref.per_v)
        else:
            assert np.array_equal(result.per_edge, ref.per_edge)
    else:
        assert np.array_equal(result.numbers, ref.numbers)
        assert result.side == ref.side


def test_concurrent_mixed_queries_bitwise_identical_to_serial():
    """N threads x mixed count/peel against two registered graphs:
    every response bitwise-matches the serial one-shot engines, and
    repeat shapes come from the cache without cross-query poisoning."""
    oracle = _serial_oracle()
    service = ButterflyService(workers=4, queue_cap=64)
    service.register("g1", G1)
    service.register("g2", G2)
    try:
        queries = MIX * 5  # 30 queries, every shape repeated 5x
        with cf.ThreadPoolExecutor(max_workers=8) as pool:
            responses = list(pool.map(service.query, queries))
        for q, r in zip(queries, responses):
            _check_against_oracle(q, r.result, oracle)
        assert service.stats()["shed"] == 0
        # cache returns shared references: verify repeated reads of the
        # same entry still match the oracle (no cross-query mutation)
        for q in MIX:
            r = service.query(q)
            assert r.service.cache == "hit"
            _check_against_oracle(q, r.result, oracle)
        assert service.cache.stats()["hits"] >= len(MIX)
    finally:
        service.close()


@needs_faults
def test_overload_sheds_typed_and_accepted_queries_stay_correct():
    """Offered load >= 2x capacity with the overload fault pinning
    workers: every submit either executes correctly or sheds with
    typed AdmissionRejected — nothing hangs, nothing corrupts."""
    oracle = _serial_oracle()
    service = ButterflyService(workers=2, queue_cap=2)
    service.register("g1", G1)
    service.register("g2", G2)
    try:
        service.query(MIX[0])  # warm one shape so hits stay cheap
        offered = MIX * 4  # 24 >= 2x the capacity of 4
        sheds, futs = 0, []
        with faults.inject("overload", site="serve.worker",
                           delay=0.05) as f:
            for q in offered:
                try:
                    futs.append((q, service.submit(q)))
                except AdmissionRejected as e:
                    assert e.capacity == 4
                    sheds += 1
            for q, fut in futs:
                r = fut.result(timeout=120)
                _check_against_oracle(q, r.result, oracle)
        assert f.fired > 0
        assert sheds > 0, "2x offered load must shed something"
        assert sheds + len(futs) == len(offered)
        assert service.stats()["shed"] == sheds
    finally:
        service.close()


@needs_faults
def test_slow_rung_under_deadline_degrades_never_corrupts():
    """slow_rung faults burning the budget inside the fused rung: the
    service degrades to cheaper rungs or serves stale/typed — accepted
    answers stay bitwise-identical to the engines."""
    oracle = _serial_oracle()
    service = ButterflyService(workers=2, queue_cap=8)
    service.register("g1", G1)
    try:
        q = Query(graph="g1", kind="count", mode="vertex",
                  deadline_s=0.3)
        service.query(Query(graph="g1", kind="count", mode="vertex"))
        service.cache.invalidate_version(service.registered()["g1"])
        outcomes = {"ok": 0, "stale": 0, "typed": 0}
        with faults.inject("slow_rung", site="count.fused",
                           delay=0.35) as f:
            for _ in range(4):
                service.cache.invalidate_version(
                    service.registered()["g1"]
                )
                try:
                    r = service.query(q)
                except res.ResilienceError:
                    outcomes["typed"] += 1
                    continue
                if r.service.cache == "stale":
                    outcomes["stale"] += 1
                else:
                    outcomes["ok"] += 1
                    _check_against_oracle(q, r.result, oracle)
        assert f.fired > 0
        assert sum(outcomes.values()) == 4
    finally:
        service.close()


# ---------------------------------------------------------------------------
# the approximate tier (accuracy="approx"): sampled answers under
# deadline pressure, marked explicitly, refined behind the response
# ---------------------------------------------------------------------------


def test_approx_query_validation_is_typed():
    with pytest.raises(ValueError, match="accuracy"):
        Query(graph="g", accuracy="nope").validate()
    with pytest.raises(ValueError, match="approx"):
        Query(graph="g", kind="peel_tips", accuracy="approx").validate()
    with pytest.raises(ValueError, match="approx"):
        Query(graph="g", mode="vertex", accuracy="approx").validate()
    with pytest.raises(ValueError, match="eps"):
        Query(graph="g", accuracy="approx", eps=0.0).validate()
    # approx keys never collide with exact keys
    qa = Query(graph="g", accuracy="approx")
    assert qa.cache_key() != Query(graph="g").cache_key()
    assert qa.exact_equivalent().cache_key() == Query(graph="g").cache_key()


def test_approx_tight_deadline_answers_from_sample():
    service = ButterflyService(workers=1, refine_approx=False)
    service.register("g", G1)
    exact = int(count_butterflies(G1, mode="global").total)
    try:
        q = Query(graph="g", accuracy="approx", eps=0.1,
                  deadline_s=1e-6, allow_stale=False)
        r = service.query(q)
        assert isinstance(r.result, ApproxCount)
        assert r.service.approximate
        assert r.service.final_rung == "sample"
        assert r.service.estimator.startswith("approx(method=sample")
        assert not r.service.refining  # refine_approx=False
        assert any("deadline-skipped" in t for t in r.service.rungs_tried)
        # routing test, not a statistics test (tests/test_sparsify.py
        # owns coverage): just require a sane same-ballpark estimate
        assert abs(r.result.estimate - exact) / exact < 0.5
        assert r.result.ci95 > 0
        assert "approximate" in r.service.summary()
        # the estimate is cached under its own approx-suffixed key...
        r2 = service.query(q)
        assert r2.service.cache == "hit" and r2.service.approximate
        # ...and never satisfies the exact-keyed query
        r3 = service.query(Query(graph="g"))
        assert r3.service.cache == "miss"
        assert int(r3.result.total) == exact
        # once the exact answer exists, the same approx query upgrades
        r4 = service.query(q)
        assert r4.service.cache == "hit" and not r4.service.approximate
        assert int(r4.result.total) == exact
        assert service.stats()["approx_served"] == 1
    finally:
        service.close()


def test_approx_without_pressure_stays_exact():
    service = ButterflyService(workers=1, refine_approx=False)
    service.register("g", G1)
    try:
        r = service.query(Query(graph="g", accuracy="approx"))
        assert not r.service.approximate
        assert r.service.final_rung == "fused"
        ref = int(count_butterflies(G1, mode="global").total)
        assert int(r.result.total) == ref
    finally:
        service.close()


def test_approx_refine_behind_upgrades_to_exact():
    service = ButterflyService(workers=2, refine_approx=True)
    service.register("g", G2)
    try:
        q = Query(graph="g", accuracy="approx", eps=0.1,
                  deadline_s=1e-6, allow_stale=False)
        r = service.query(q)
        assert r.service.approximate and r.service.refining
        stop = time.monotonic() + 30.0
        while time.monotonic() < stop:
            with service._lock:
                busy = bool(service._refining)
            if not busy:
                break
            time.sleep(0.01)
        assert not busy, "refine-behind never completed"
        r2 = service.query(q)
        assert r2.service.cache == "hit" and not r2.service.approximate
        ref = int(count_butterflies(G2, mode="global").total)
        assert int(r2.result.total) == ref
        # the refine is deduped: a racing repeat spawns at most one
        assert service.stats()["served"] >= 3  # approx + refine + hit
    finally:
        service.close()
