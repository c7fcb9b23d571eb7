"""CPU self-check of the span and scope readers (``scopes.py``) and the
metrics that read them: ``python -m pytest benchmarks/chip``.

Synthetic windows: two programs that both hold ``fusion.1``, nested
host spans, several jobs, and an eager operation after a program's
fetch; then a compiled program of the library, whose instructions the
scope table has to cover.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from benchmarks.chip import run as harness  # noqa: E402
from benchmarks.chip import scopes  # noqa: E402
from benchmarks.chip import trace as trace_mod  # noqa: E402

TABLES = {  # what compiling the two programs would give
    "prog_a": {"while.1": ("recover", "calls"),
               "fusion.1": ("recover", "op_name"),
               "fusion.2": ("accumulate", "op_name")},
    "prog_b": {"fusion.1": ("subtract", "op_name"),
               "fusion.3": ("bucket_update", "users")},
}


U = 100_000  # ns: one step of the synthetic timeline is 0.1 ms


def _job(t):
    """One job at ``t`` steps: host spans (rank, then preprocess with a
    plan nested in it, then a plan), program A launched and fetched,
    program B launched and fetched, and an eager op after B's fetch."""
    host = [
        ["bench.job", t, 1000],
        ["repro.peel_tips", t + 5, 990],
        ["repro.rank", t + 10, 40],
        ["repro.preprocess", t + 50, 30],
        ["repro.plan", t + 60, 10],  # inside preprocess: not counted
        ["repro.plan", t + 80, 40],
        ["repro.launch.prog_a", t + 120, 5],
        ["repro.fetch", t + 125, 185],
        ["repro.launch.prog_b", t + 320, 5],
        ["repro.fetch", t + 326, 74],
        ["python_frame", t + 400, 300],
    ]
    device = [
        ["%while.1 = (s32[]) while(...)", t + 130, 170],
        ["%fusion.1 = s32[8] fusion(...)", t + 140, 60],  # A: recover
        ["%fusion.2 = s32[8] fusion(...)", t + 210, 50],  # A: accumulate
        ["%fusion.1 = s32[8] fusion(...)", t + 330, 50],  # B: subtract
        ["%fusion.3 = s32[8] fusion(...)", t + 380, 10],  # B: bucket_update
        ["%fusion.1 = s32[8] fusion(...)", t + 500, 10],  # eager: no program
    ]
    return ([[n, s * U, d * U] for n, s, d in host],
            [[n, s * U, d * U] for n, s, d in device])


def _run(jobs=3, spans=True):
    host, device = [], []
    for k in range(jobs):
        h, d = _job(k * 2000)
        host += h if spans else [e for e in h if not e[0].startswith("repro.")]
        device += d
    run = harness.Run("TPU v5 lite")
    run.trace = trace_mod.Reduced.from_events([device], host, "bench.job")
    run.programs = [("prog_a", (), {}), ("prog_b", (), {})]
    run.jobs = jobs
    run._program_scopes = TABLES
    return run


def _read(metric, run):
    return harness.load_module("metrics", metric).read(run)


def test_host_prep_counts_outermost_spans_per_job():
    for jobs in (1, 3):
        run = _run(jobs)
        want = (40 + 30 + 40) * U * 1e-9
        assert _read("host_prep_s.count", run) == pytest.approx(want)
        assert _read("host_prep_s.decomp", run) == pytest.approx(want)


def test_host_syncs_counts_fetches_per_job():
    assert _read("host_syncs.decomp", _run(3)) == pytest.approx(2.0)


def test_colliding_instructions_take_their_programs_scope():
    run = _run(2)
    table = scopes.scope_self_ns(run)
    # A's fusion.1 is recover, B's is subtract, the eager one has none
    assert table[("prog_a", "fusion.1")] == ("recover", pytest.approx(120 * U))
    assert table[("prog_b", "fusion.1")] == ("subtract",
                                             pytest.approx(100 * U))
    assert table[(None, "fusion.1")] == (None, pytest.approx(20 * U))
    # the while's self time excludes its body
    assert table[("prog_a", "while.1")] == ("recover", pytest.approx(120 * U))
    busy = run.trace.busy_s
    assert busy == pytest.approx(2 * (170 + 50 + 10 + 10) * U * 1e-9)
    recover = 100 * (2 * (60 + 60)) * U * 1e-9 / busy
    accumulate = 100 * (2 * 50) * U * 1e-9 / busy
    assert _read("recover.busy_share.count", run) == pytest.approx(recover)
    assert _read("recover.busy_share.decomp", run) == pytest.approx(recover)
    assert _read("accumulate.busy_share.count", run) == pytest.approx(
        accumulate)


def test_breakdown_names_gaps_by_span():
    bd = scopes.breakdown(_run(2))
    assert bd["by_scope"]["unscoped"] == pytest.approx(20 * U * 1e-9)
    assert bd["unscoped"] == [[None, "fusion.1", pytest.approx(20 * U * 1e-9)]]
    assert 0 < bd["scoped_share"] < 100
    gaps = dict((round(s * 1e9 / U), n) for n, s in bd["idle_gaps"])
    assert gaps[130] == "repro.plan"  # mid-gap, the innermost span
    assert gaps[30] == "repro.peel_tips"  # between the two programs
    # the first gap of a job, split at span edges: rank 10-50,
    # preprocess 50-60 and 70-80, its nested plan 60-70, plan 80-120
    idle = {k: round(v * 1e9 / U) for k, v in bd["idle_by_span"].items()}
    assert idle["repro.rank"] == 40 and idle["repro.plan"] == 50
    assert idle["repro.preprocess"] == 20 and idle["repro.peel_tips"] > 0
    # the eager op after B's fetch shares an instruction name with B,
    # but does not follow B's work back to back: no clock miss
    assert bd["clock_misses"] == []


def test_clock_miss_when_device_work_outlasts_the_fetch():
    run = _run(1)
    run.trace.device[0].append(
        ["%fusion.2 = s32[8] fusion(...)", 305 * U, 300 * U])
    misses = scopes.clock_misses(run)
    assert misses[0][:2] == ["prog_a", "fusion.2"]
    assert misses[0][2] == pytest.approx(295 * U * 1e-9)


def test_device_work_just_before_its_launch_is_its_programs():
    """The device timeline may lead the host's by a fraction of a
    millisecond: an operation of B's that starts 0.5 ms before B's
    launch span and runs into B's next one is still B's, and counts as
    a 0.5 ms miss."""
    run = _run(1)
    early = ["%fusion.3 = s32[8] fusion(...)", 320 * U - 500_000,
             10 * U + 500_000]  # runs up to B's first operation
    run.trace.device[0].append(early)
    events = scopes.program_events(run)
    assert early in events["prog_b"]
    misses = scopes.clock_misses(run)
    assert ["prog_b", "fusion.3", pytest.approx(0.5e-3)] in misses


def test_readers_fall_silent_without_the_tap():
    run = _run(2, spans=False)
    for metric in ("host_prep_s.count", "host_prep_s.decomp",
                   "host_syncs.decomp", "recover.busy_share.count",
                   "accumulate.busy_share.count",
                   "recover.busy_share.decomp"):
        assert _read(metric, run) is None, metric


def test_instruction_scopes_of_hlo_text():
    text = """HloModule m

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/match/add"}
}

%wrapped (param_0.1: s32[8]) -> s32[8] {
  ROOT %rw = s32[8]{0} reduce-window(%param_0.1)
}

ENTRY %main (p: s32[8]) -> (s32[8], /*index=1*/s32[8]) {
  %p = s32[8]{0} parameter(0)
  %w = s32[8]{0} fusion(%p), kind=kLoop, calls=%wrapped
  %f = s32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation
  %g = s32[8]{0} fusion(%f), kind=kLoop, calls=%wrapped, metadata={op_name="jit(f)/recover/accumulate/scatter-add"}
  %h = s32[8]{0} fusion(%g), kind=kLoop, calls=%wrapped, metadata={op_name="jit(f)/while/add"}
  ROOT %t = (s32[8]{0}, /*index=1*/s32[8]{0}) tuple(%f, %h)
}
"""
    got = scopes.instruction_scopes(text, ("recover", "match", "accumulate"))
    assert got["f"] == ("match", "calls")
    assert got["g"] == ("accumulate", "op_name")  # innermost wins
    assert got["w"] == ("match", "users")
    assert got["h"] == ("accumulate", "operands")
    assert scopes.coverage(text, ("recover", "match", "accumulate")) == {
        "users": 1, "calls": 1, "op_name": 1, "operands": 1}


def test_a_compiled_program_is_covered():
    import jax
    import numpy as np

    from repro.core import BipartiteGraph, count_butterflies
    from repro.core.pipeline import DEVICE_SCOPES, record_programs

    rng = np.random.default_rng(3)
    e = np.unique(np.stack([rng.integers(0, 30, 200),
                            rng.integers(0, 20, 200)], 1), axis=0)
    with record_programs() as programs:
        count_butterflies(BipartiteGraph(30, 20, e), mode="all",
                          engine="fused_pallas")
    (name, args, kwargs), = [(p.__name__, a, k) for p, a, k in programs]
    text = scopes.resolve(name).lower(*args, **kwargs).compile().as_text()
    cov = scopes.coverage(text, DEVICE_SCOPES)
    assert cov.get(None, 0) == 0 and cov["op_name"] >= 0.8 * sum(cov.values())
    assert jax.numpy.int32  # the library ran on this backend
