"""Chaos matrix for the resilient execution layer.

Every injected fault — hash overflow, capacity overflow, OOM, a
poisoned tile, a lost device worker — must yield either bitwise parity
with the clean run (a lower rung or a retry carried the workload) or a
typed :class:`~repro.core.resilience.ResilienceError`. Never a silent
wrong answer. Plus: graph-validation property tests (malformed inputs
raise :class:`GraphValidationError` before any kernel runs), the
accumulator preflight, and the :class:`ExecutionReport` audit trail.

The device-loss subprocess cells that need a full jax worker are gated
on ``REPRO_FAULTS=1`` (the CI fault-injection job); the fast cells run
in tier-1.
"""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    AccumulatorOverflowRisk,
    BipartiteGraph,
    CapacityOverflow,
    DeviceLost,
    GraphValidationError,
    ResilienceError,
    ResiliencePolicy,
    ResourceExhausted,
    count_butterflies,
    preprocess,
)
from repro.core.distributed import launch_device_worker
from repro.core.oracle import global_count
from repro.core.peel import peel_tips, peel_tips_stored, peel_wings
from repro.core.resilience import (
    ExecutionReport,
    ResultInvariantViolation,
    Rung,
    RungUnavailable,
    resolve_policy,
)
from repro.testing import faults

FAULTS_ENABLED = os.environ.get("REPRO_FAULTS") == "1"
needs_faults_job = pytest.mark.skipif(
    not FAULTS_ENABLED, reason="full-worker device-loss cells run in the "
    "REPRO_FAULTS=1 CI job"
)


def rand_graph(nu, nv, m, seed):
    rng = np.random.default_rng(seed)
    e = np.stack([rng.integers(0, nu, m), rng.integers(0, nv, m)], axis=1)
    return BipartiteGraph(nu, nv, e)


GRAPH = rand_graph(30, 20, 260, 7)


# ---------------------------------------------------------------------------
# The chaos matrix: {fault} x {count, peel_tips, peel_tips_stored,
# peel_wings}. Each workload entry is (runner, device_site). The runner
# returns the host numbers array; parity cells compare it bitwise
# against the same runner's clean output.
# ---------------------------------------------------------------------------


def _run_count(g, **kw):
    r = count_butterflies(g, mode="vertex", engine="fused_pallas", **kw)
    return np.asarray(r.per_u), r.report


def _run_tips(g, **kw):
    r = peel_tips(g, side=0, engine="device", **kw)
    return np.asarray(r.numbers), r.report


def _run_tips_stored(g, **kw):
    r = peel_tips_stored(g, side=0, engine="device", **kw)
    return np.asarray(r.numbers), r.report


def _run_wings(g, **kw):
    r = peel_wings(g, engine="device", **kw)
    return np.asarray(r.numbers), r.report


WORKLOADS = {
    "count": (_run_count, "count.fused_pallas", "count."),
    "peel_tips": (_run_tips, "peel_tips.device", "peel_tips."),
    "peel_tips_stored": (
        _run_tips_stored, "peel_tips_stored.device", "peel_tips_stored."
    ),
    "peel_wings": (_run_wings, "peel_wings.device", "peel_wings."),
}


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_chaos_hash_overflow_parity(name):
    """Forced 4-slot hash tables: the in-graph sort fallback carries
    every round, results stay bitwise."""
    if name == "count":
        # the fused_pallas kernel aggregates in-VMEM without the hash
        # table; the fused engine is the counting rung with the
        # bounded-probe table + in-graph sort fallback
        def run(g, **kw):
            r = count_butterflies(g, mode="vertex", engine="fused", **kw)
            return np.asarray(r.per_u), r.report
    else:
        run, _dev, _all = WORKLOADS[name]
    clean, _ = run(GRAPH, aggregation="hash")
    with faults.inject("hash_overflow", bits=2) as f:
        got, report = run(GRAPH, aggregation="hash")
    assert f.fired > 0  # the tiny table really was forced
    assert np.array_equal(got, clean)
    assert report.final_rung is not None


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_chaos_capacity_overflow_descends_with_parity(name):
    """A forced tiny capacity budget trips the overflow latch / tile
    bound: the ladder must descend to the next rung and stay bitwise."""
    run, dev_site, _all = WORKLOADS[name]
    kw = {} if name == "count" else {"subtract": "materialize"}
    clean, _ = run(GRAPH, **kw)
    with faults.inject("capacity_overflow", site=dev_site, budget=1):
        got, report = run(GRAPH, **kw)
    assert np.array_equal(got, clean)
    assert report.degraded, report.summary()
    assert report.attempts[0].outcome == "capacity-overflow"


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_chaos_transient_oom_retries_same_rung(name):
    """A transient RESOURCE_EXHAUSTED (times=1) is absorbed by the
    shrink-retry on the same rung: no degradation, bitwise parity."""
    run, dev_site, _all = WORKLOADS[name]
    clean, _ = run(GRAPH)
    with faults.inject("oom", site=dev_site, times=1):
        got, report = run(GRAPH)
    assert np.array_equal(got, clean)
    assert not report.degraded, report.summary()
    assert report.retries == 1
    assert report.final_budget_shrinks == 1


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_chaos_hard_oom_is_typed_never_silent(name):
    """A hard OOM on every rung exhausts the ladder: the failure
    surfaces as the typed ResourceExhausted, not a wrong answer."""
    run, _dev, all_site = WORKLOADS[name]
    with pytest.raises(ResourceExhausted):
        with faults.inject("oom", site=all_site):
            run(GRAPH)


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_chaos_poisoned_tile_demotes_with_parity(name):
    """A sentinel-poisoned buffer violates the result invariants: the
    validator demotes the rung and the clean rung restores parity."""
    run, dev_site, _all = WORKLOADS[name]
    poison_site = "count.fused_pallas" if name == "count" else dev_site
    clean, _ = run(GRAPH)
    with faults.inject("poison", site=poison_site):
        got, report = run(GRAPH)
    assert np.array_equal(got, clean)
    assert report.degraded, report.summary()
    assert any(a.outcome == "invalid-result" for a in report.attempts)


def test_poison_with_validation_disabled_never_returned_silently():
    """resilience=False drops validation — the poison then flows into
    the result. This cell documents exactly what the default policy is
    protecting against (and that the default catches it)."""
    clean, _ = _run_tips(GRAPH)
    with faults.inject("poison", site="peel_tips.device"):
        r = peel_tips(GRAPH, side=0, engine="device", resilience=False)
    # the unvalidated run really is corrupt -> the validator is load-
    # bearing, not decorative
    assert not np.array_equal(np.asarray(r.numbers), clean)


# ---------------------------------------------------------------------------
# Device loss (subprocess workers)
# ---------------------------------------------------------------------------


def test_device_loss_hard_raises_typed_with_index():
    """A worker that dies on every attempt surfaces as DeviceLost
    carrying the failed device index and attempt count (fast: the
    injected death happens before the child imports jax)."""
    with pytest.raises(DeviceLost) as ei:
        with faults.inject("device_loss"):
            launch_device_worker(
                "print('unreachable')", device_index=2, retries=1,
                backoff_s=0.01, timeout_s=120,
            )
    assert ei.value.device == 2
    assert ei.value.attempts == 2
    assert isinstance(ei.value, RuntimeError)  # taxonomy compat


def test_device_loss_targets_only_the_faulted_device():
    """A device-scoped fault must not kill other workers."""
    with faults.inject("device_loss", device=5):
        out = launch_device_worker(
            "print('OK0')", device_index=0, retries=0, timeout_s=120
        )
    assert "OK0" in out


@needs_faults_job
def test_device_loss_transient_retry_recovers_parity():
    """times=1 kills only the first attempt; the retry reruns the full
    jax worker and the counted total matches the in-process oracle."""

    code = (
        "import numpy as np\n"
        "from repro.core import BipartiteGraph, count_butterflies\n"
        "rng = np.random.default_rng(7)\n"
        "e = np.stack([rng.integers(0, 30, 260),"
        " rng.integers(0, 20, 260)], axis=1)\n"
        "g = BipartiteGraph(30, 20, e)\n"
        "print('TOTAL', int(count_butterflies(g).total))\n"
    )
    with faults.inject("device_loss", times=1):
        out = launch_device_worker(code, retries=1, backoff_s=0.05)
    total = int(out.split("TOTAL")[1].strip())
    assert total == global_count(GRAPH)


@needs_faults_job
def test_device_loss_hang_times_out_typed():
    """A hung worker trips the per-attempt timeout and surfaces as
    DeviceLost, not an indefinite stall."""
    with pytest.raises(DeviceLost, match="timed out"):
        with faults.inject("device_loss", mode="hang"):
            launch_device_worker("print('X')", retries=0, timeout_s=3)


# ---------------------------------------------------------------------------
# Graph validation: malformed inputs never reach a kernel
# ---------------------------------------------------------------------------

MALFORMATIONS = (
    "empty_u", "empty_v", "negative_endpoint", "oob_u", "oob_v",
    "duplicate_raise", "ragged_csr", "nonmonotone_csr", "bad_order",
)


def _build_malformed(kind, n_u, n_v, m, seed):
    rng = np.random.default_rng(seed)
    e = np.stack(
        [rng.integers(0, n_u, m), rng.integers(0, n_v, m)], axis=1
    )
    if kind == "empty_u":
        BipartiteGraph(0, n_v, np.zeros((0, 2), np.int64))
    elif kind == "empty_v":
        BipartiteGraph(n_u, 0, np.zeros((0, 2), np.int64))
    elif kind == "negative_endpoint":
        bad = e.copy()
        bad[rng.integers(0, m), rng.integers(0, 2)] = -1
        BipartiteGraph(n_u, n_v, bad)
    elif kind == "oob_u":
        bad = e.copy()
        bad[rng.integers(0, m), 0] = n_u
        BipartiteGraph(n_u, n_v, bad)
    elif kind == "oob_v":
        bad = e.copy()
        bad[rng.integers(0, m), 1] = n_v + int(rng.integers(0, 3))
        BipartiteGraph(n_u, n_v, bad)
    elif kind == "duplicate_raise":
        dup = np.concatenate([e, e[:1]])
        BipartiteGraph(n_u, n_v, dup, on_duplicate="raise")
    elif kind == "ragged_csr":
        indptr = np.arange(n_u + 1)  # claims n_u indices
        indices = np.zeros(n_u + 1 + int(rng.integers(1, 4)), np.int64)
        BipartiteGraph.from_csr(indptr, indices, n_v)
    elif kind == "nonmonotone_csr":
        indptr = np.arange(n_u + 1)
        indptr[int(rng.integers(1, n_u))] = 0
        indptr[0] = 0
        BipartiteGraph.from_csr(indptr, np.zeros(n_u - 1, np.int64), n_v)
    elif kind == "bad_order":
        g = BipartiteGraph(n_u, n_v, e)
        order = np.zeros(g.n, np.int64)  # not a permutation
        preprocess(g, order)
    else:  # pragma: no cover
        raise AssertionError(kind)


@settings(max_examples=20, deadline=None)
@given(
    kind=st.sampled_from(MALFORMATIONS),
    n_u=st.integers(2, 12),
    n_v=st.integers(2, 9),
    m=st.integers(3, 40),
    seed=st.integers(0, 2**16),
)
def test_property_malformed_graphs_never_reach_a_kernel(
    kind, n_u, n_v, m, seed
):
    """Every malformation class raises the typed GraphValidationError
    at construction/preprocess time — upstream of any kernel dispatch —
    and stays catchable as the ValueError it also subclasses."""
    with pytest.raises(GraphValidationError):
        _build_malformed(kind, n_u, n_v, m, seed)
    with pytest.raises(ValueError):  # taxonomy compat
        _build_malformed(kind, n_u, n_v, m, seed)


def test_csr_roundtrip_and_duplicate_policies():
    g = rand_graph(10, 8, 40, 1)
    indptr = np.zeros(11, np.int64)
    np.add.at(indptr[1:], g.edges[:, 0], 1)
    indptr = np.cumsum(indptr)
    order = np.lexsort((g.edges[:, 1], g.edges[:, 0]))
    indices = g.edges[order, 1]
    g2 = BipartiteGraph.from_csr(indptr, indices, 8)
    assert np.array_equal(
        np.sort(g2.edges, axis=0), np.sort(g.edges, axis=0)
    )
    # dedupe (default) silently drops; assume_unique skips the pass
    dup = np.concatenate([g.edges, g.edges[:3]])
    assert BipartiteGraph(10, 8, dup).m == g.m
    assert BipartiteGraph(
        10, 8, g.edges, on_duplicate="assume_unique"
    ).m == g.m
    with pytest.raises(GraphValidationError, match="duplicate"):
        BipartiteGraph(10, 8, dup, on_duplicate="raise")


def test_accumulator_preflight():
    g = rand_graph(40, 30, 400, 2)
    bound = g.accumulator_preflight()  # default 2^63 budget: fine
    assert global_count(g) <= bound
    # the bound W (d2 - 1) / 2 of the tighter side, not C(W, 2)
    w = min(g.wedge_totals())
    assert bound < w * (w - 1) // 2
    with pytest.raises(AccumulatorOverflowRisk):
        g.accumulator_preflight(budget_bits=4)
    with pytest.raises(OverflowError):  # taxonomy compat
        g.accumulator_preflight(budget_bits=4)


# ---------------------------------------------------------------------------
# ExecutionReport / policy mechanics
# ---------------------------------------------------------------------------


def test_report_attached_and_summary_readable():
    r = count_butterflies(GRAPH, engine="fused_pallas")
    assert isinstance(r.report, ExecutionReport)
    assert r.report.requested == "fused_pallas"
    assert r.report.final_rung == "fused_pallas"
    assert not r.report.degraded
    assert "fused_pallas[ok]" in r.report.summary()
    p = peel_tips(GRAPH, side=0, engine="device")
    assert p.report.workload == "peel_tips"
    assert p.report.final_rung == "device"


def test_resilience_false_disables_report_and_validation():
    r = count_butterflies(GRAPH, resilience=False)
    assert r.report is None
    p = peel_wings(GRAPH, resilience=False)
    assert p.report is None
    # descent is engine semantics, not a policy extra: a capped device
    # run still falls back to host with the policy disabled
    capped = peel_tips(
        GRAPH, side=0, engine="device", max_frontier=1,
        subtract="materialize", resilience=False,
    )
    want = peel_tips(GRAPH, side=0)
    assert np.array_equal(capped.numbers, want.numbers)


def test_custom_policy_backoff_and_retry_budget():
    sleeps = []
    pol = ResiliencePolicy(max_retries=3, backoff_base_s=0.5,
                           sleep=sleeps.append)
    calls = []

    def flaky(shrinks):
        calls.append(shrinks)
        if len(calls) < 3:
            raise ResourceExhausted("RESOURCE_EXHAUSTED: injected")
        return "ok"

    out, report = pol.execute("w", [Rung("r", flaky)])
    assert out == "ok"
    assert calls == [0, 1, 2]  # budget halves once per retry
    assert sleeps == [0.5, 1.0]  # exponential backoff
    assert report.retries == 2


def test_ladder_exhaustion_raises_invariant_violation():
    pol = ResiliencePolicy(backoff_base_s=0.0)
    bad = Rung("bad", lambda s: "corrupt")
    with pytest.raises(ResultInvariantViolation, match="corrupt-detail"):
        pol.execute("w", [bad], lambda out: "corrupt-detail")


def test_rung_unavailable_descends_then_raises_at_bottom():
    pol = ResiliencePolicy(backoff_base_s=0.0)

    def never(s):
        raise RungUnavailable("statically inapplicable")

    out, report = pol.execute(
        "w", [Rung("a", never), Rung("b", lambda s: 42)]
    )
    assert out == 42 and report.degraded
    with pytest.raises(RungUnavailable):
        pol.execute("w", [Rung("a", never)])


def test_capacity_overflow_is_valueerror_compat():
    with pytest.raises(ValueError, match="fused"):
        raise CapacityOverflow("engine='fused_pallas' tile bound; use "
                               "engine='fused'")
    assert issubclass(GraphValidationError, ValueError)
    assert issubclass(ResourceExhausted, MemoryError)
    assert issubclass(CapacityOverflow, ResilienceError)


def test_resolve_policy_contract():
    assert resolve_policy(None) is resolve_policy(True)
    assert not resolve_policy(False).validate_results
    pol = ResiliencePolicy(max_retries=9)
    assert resolve_policy(pol) is pol
    with pytest.raises(ValueError):
        resolve_policy("aggressive")
