"""CPU self-check of the chip benchmark: ``python -m pytest benchmarks/chip``.

The references against the program's dense oracle and host peeling
engine; each cell's job run through the harness at a tiny size with the
chip check skipped; the lower-precision controls and faults planted in
the program, which the comparison has to catch; the trace reduction on
a small recorded trace; and the refusals without a chip.
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from benchmarks.chip import checks, graphs, reference, roofline  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402
from benchmarks.chip import trace as trace_mod  # noqa: E402

TINY = {"n_u": 90, "n_v": 120, "m": 700, "alpha_u": 2.1, "alpha_v": 2.1,
        "graph_seed": 3}
CELLS = ["github-count-cacheopt", "condmat-tips", "github-count-default"]
TRACE = HERE / "testdata" / "count_trace.json.gz"


def _graph(seed, n_u=60, n_v=80, m=500):
    from repro.core.graph import BipartiteGraph

    e = graphs.relabel(graphs.powerlaw_edges(n_u, n_v, m, 2.1, 2.1, seed),
                       n_u, n_v, seed + 100)
    return BipartiteGraph(n_u, n_v, e, on_duplicate="raise"), e


def _tiny_plan(cell: str, config: dict = TINY) -> dict:
    plan = harness.cell_plan(harness.load_spec(), cell)
    return dict(plan, config=dict(plan["config"], **config))


# -- the yardstick ---------------------------------------------------------


def test_relabel_keeps_the_ranked_graph():
    from repro.core.graph import BipartiteGraph, preprocess
    from repro.core.ranking import make_order

    base = graphs.powerlaw_edges(300, 200, 2000, 2.1, 2.1, 1)
    seen = set()
    for seed in (0, 2**35 + 9, -4):
        e = graphs.relabel(base, 300, 200, seed)
        g = BipartiteGraph(300, 200, e, on_duplicate="raise")
        rg = preprocess(g, make_order(g, "degree"))
        seen.add((rg.offsets.tobytes(), rg.neighbors.tobytes()))
    assert len(seen) == 1


def test_generator_tops_up_to_unique_edges():
    e = graphs.powerlaw_edges(50, 40, 900, 2.1, 2.1, 7)
    assert e.shape == (900, 2)
    assert np.unique(e[:, 0] * 40 + e[:, 1]).size == 900
    r = graphs.relabel(e, 50, 40, 2**40 + 3)
    assert sorted(np.bincount(r[:, 0], minlength=50)) == sorted(
        np.bincount(e[:, 0], minlength=50))
    assert np.array_equal(r, graphs.relabel(e, 50, 40, 2**40 + 3))


@pytest.mark.parametrize("name", ["github", "condmat"])
def test_config_states_its_graph(name):
    """A configuration's graph has the butterflies, largest degrees and
    (for the peeled one) tip rounds that its file states beside the
    published count it was fit to."""
    with open(HERE / "configs" / f"{name}.json") as f:
        config = json.load(f)
    e = graphs.build_edges(config, 2**33 + 5)
    gen = config["generated"]
    ref = reference.count_reference(config["n_u"], config["n_v"], e)
    assert int(ref["total"]) == gen["butterflies"]
    assert np.bincount(e[:, 0]).max() == gen["max_degree_u"]
    assert np.bincount(e[:, 1]).max() == gen["max_degree_v"]
    if "tip_rounds" in gen:
        tips = reference.tips_reference(config["n_u"], config["n_v"], e)
        assert (tips["rounds"], int(tips["numbers"].max())) == (
            gen["tip_rounds"], gen["max_tip"])


def test_generator_first_draw_is_the_programs():
    from repro.data.graphs import powerlaw_bipartite

    g = powerlaw_bipartite(300, 200, 800, seed=5)
    assert g.m < 800  # the draw collapsed under duplicates
    e = graphs.powerlaw_edges(300, 200, 800, 2.1, 2.1, 5)
    assert np.array_equal(e[:g.m], g.edges)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_count_reference_matches_oracle(seed):
    from repro.core import oracle

    g, e = _graph(seed)
    ref = reference.count_reference(g.n_u, g.n_v, e)
    pu, pv = oracle.per_vertex_counts(g)
    assert int(ref["total"]) == oracle.global_count(g)
    assert np.array_equal(ref["per_u"], pu)
    assert np.array_equal(ref["per_v"], pv)
    assert np.array_equal(ref["per_edge"], oracle.per_edge_counts(g))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tips_reference_matches_host_engine(seed):
    from repro.core import oracle
    from repro.core.peel import peel_tips

    g, e = _graph(seed)
    ref = reference.tips_reference(g.n_u, g.n_v, e)
    host = peel_tips(g)
    assert ref["side"] == host.side
    assert np.array_equal(ref["numbers"], host.numbers)
    assert ref["rounds"] == host.rounds
    pu, pv = oracle.per_vertex_counts(g)
    assert np.array_equal(ref["counts"], pu if ref["side"] == 0 else pv)


def test_bf16_rounding():
    x = np.array([0, 1, 255, 256, 257, 258, 511, 513, 65535, 123456789])
    got = reference._round_bf16(x)
    want = np.array([float(jax.numpy.asarray(v, jax.numpy.bfloat16))
                     for v in x])
    assert np.array_equal(got, want.astype(np.int64))


# -- each cell's job through the harness ------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_through_the_harness(cell, tmp_path):
    plan = _tiny_plan(cell)
    result, run = harness.run_cell(plan, seed=2**33 + 1, seconds=0.0,
                                   trace=False, require_accelerator=False,
                                   cache_dir=tmp_path)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {m["name"] for m in plan["end_to_end"]}
    assert set(result["metrics"]) == want and "setup_s" in want
    assert list(result)[-1] == "checks"
    assert all(c["limit"] == 0 for c in result["checks"].values())
    assert run.window_compiles == 0


# -- the controls and faults the comparison has to catch --------------------

# K(8, 3000) beside a sparse graph: per-vertex counts past 2**24, so
# float32 accumulation cannot hold them exactly.
def _dense_edges():
    us, vs = np.meshgrid(np.arange(8), np.arange(3000), indexing="ij")
    dense = np.stack([us.ravel(), vs.ravel()], axis=1)
    sparse = graphs.powerlaw_edges(200, 3000, 800, 2.1, 2.1, 1)
    sparse[:, 0] += 8
    return 208, 3000, np.concatenate([dense, sparse])


def test_count_control_fails():
    from benchmarks.chip.jobs import count

    n_u, n_v, e = _dense_edges()
    ref = reference.count_reference(n_u, n_v, e)
    low = reference.count_reference(n_u, n_v, e, acc="float32")
    got = count.readings(low, ref)
    assert any(v > count.LIMITS[k] for k, v in got.items()), got
    assert all(v == 0 for v in count.readings(ref, ref).values())


def test_tips_control_fails():
    from benchmarks.chip.jobs import tips

    _g, e = _graph(4, n_u=200, n_v=300, m=3000)
    ref = reference.tips_reference(200, 300, e)
    low = reference.tips_reference(200, 300, e, acc="bfloat16")
    got = tips.readings(low, ref)
    assert any(v > tips.LIMITS[k] for k, v in got.items()), got


def _fault_count_altered(monkeypatch):
    from repro.kernels import ops

    orig = ops.match_tiles

    def altered(*a, **k):
        dm1, c2 = orig(*a, **k)
        return dm1.at[0, 0].add(1), c2

    monkeypatch.setattr(ops, "match_tiles", altered)


def _fault_count_half_batch(monkeypatch):
    from repro.kernels import ops

    orig = ops.match_tiles

    def half(*a, **k):
        dm1, c2 = orig(*a, **k)
        keep = np.arange(dm1.shape[1]) % 2 == 0  # every other wedge lane
        return dm1 * keep, c2 * keep

    monkeypatch.setattr(ops, "match_tiles", half)


def _fault_count_unchanged(monkeypatch):
    from repro.core import pipeline

    monkeypatch.setattr(pipeline, "lane_counts",
                        lambda dg, w, dm1, c2, mode, acc: acc)


def _peel_fault(scale):
    def plant(monkeypatch):
        from repro.core import peel

        orig = peel._apply_decrements

        def faulty(b, alive, tgt, dec, *rest):
            return orig(b, alive, tgt, scale(dec), *rest)

        monkeypatch.setattr(peel, "_apply_decrements", faulty)

    return plant


FAULTS = {
    ("github-count-cacheopt", "answer_altered"): _fault_count_altered,
    ("github-count-cacheopt", "half_batch"): _fault_count_half_batch,
    ("github-count-cacheopt", "state_unchanged"): _fault_count_unchanged,
    ("github-count-default", "state_unchanged"): _fault_count_unchanged,
    ("condmat-tips", "answer_altered"): _peel_fault(
        lambda d: d.at[0].add(1)),
    ("condmat-tips", "half_batch"): _peel_fault(
        lambda d: d * (jax.numpy.arange(d.shape[0]) % 2)),
    ("condmat-tips", "state_unchanged"): _peel_fault(lambda d: d * 0),
}


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(cell, fault, monkeypatch, tmp_path):
    jax.clear_caches()
    FAULTS[(cell, fault)](monkeypatch)
    try:
        result, _run = harness.run_cell(
            _tiny_plan(cell), seed=11, seconds=0.0, trace=False,
            require_accelerator=False, cache_dir=tmp_path)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not result["correct"], result["checks"]


def test_off_path_names_a_descent():
    class Attempt:
        outcome, retries, budget_shrinks = "ok", 0, 0

    class Report:
        requested, final_rung, attempts = "fused_pallas", "fused", [Attempt()]

    class Answer:
        report = Report()

    assert "answered by fused" in checks.off_path(Answer(), [], [])
    Report.final_rung = "fused_pallas"
    assert checks.off_path(Answer(), ["run_fused_pallas_program"],
                           ["run_fused_pallas_program"]) is None
    assert "programs" in checks.off_path(Answer(), ["run_count_tiles"],
                                         ["run_fused_pallas_program"])


# -- the trace reduction, on a small trace recorded on a v5e -----------------


def test_trace_reduction_on_recorded_trace():
    with gzip.open(TRACE, "rt") as f:
        rec = json.load(f)
    red = trace_mod.Reduced.from_events(rec["device"], rec["host"],
                                        job_span="bench.job")
    want = rec["expected"]
    assert red.window_s == pytest.approx(want["window_s"])
    assert red.busy_s == pytest.approx(want["busy_s"])
    assert red.kernel_s("wedge_fused") == pytest.approx(want["wedge_fused_s"])
    assert red.host_lead_s() == pytest.approx(want["host_lead_s"])
    assert red.breakdown() == want["breakdown"]
    # the same numbers worked out another way: busy microseconds on a
    # timeline, the kernel's events by name, each job's first device op
    jobs = [(s, s + d) for n, s, d in rec["host"] if n == "bench.job"]
    w0, w1 = jobs[0][0], jobs[-1][1]
    line = np.zeros(int((w1 - w0) // 1000) + 1, bool)
    for _n, s, d in rec["device"][0]:
        lo, hi = max(s, w0), min(s + d, w1)
        if hi > lo:
            line[int((lo - w0) // 1000):int((hi - w0) // 1000) + 1] = True
    assert red.busy_s == pytest.approx(line.sum() * 1e-6, rel=0.01)
    assert 0.0 < red.busy_s <= red.window_s
    kern = sum(d for n, s, d in rec["device"][0]
               if n.startswith("%wedge_fused.") and w0 <= s < w1)
    assert red.kernel_s("wedge_fused") == pytest.approx(kern * 1e-9)
    leads = [min(s for _n, s, _d in rec["device"][0] if s >= js) - js
             for js, _je in jobs]
    assert red.host_lead_s() == pytest.approx(np.mean(leads) * 1e-9)
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"] == sorted(bd["device_ops"], key=lambda x: -x[1])


def test_busy_union_merges_overlaps():
    red = trace_mod.Reduced.from_events(
        [[["a", 0, 10], ["b", 5, 10], ["wedge_fused", 30, 5]]],
        [["bench.job", 0, 40]], job_span="bench.job")
    assert red.busy_s == pytest.approx(20e-9)
    assert red.window_s == pytest.approx(40e-9)
    assert red.idle_pct() == pytest.approx(50.0)
    assert red.kernel_s("wedge_fused") == pytest.approx(5e-9)
    assert red.host_lead_s() == pytest.approx(0.0)


def test_roofline_share_and_unknown_chip():
    ops, nbytes = roofline.wedge_fused_work(10**6)
    t = roofline.least_time("TPU v5 lite", ops, nbytes)
    assert t == pytest.approx(16e6 / 819e9)
    assert roofline.share_pct("TPU v5 lite", ops, nbytes, 2 * t) == 50.0
    assert roofline.share_pct("TPU v5 lite", ops, nbytes, 0.0) is None
    with pytest.raises(KeyError):
        roofline.least_time("TPU v99", ops, nbytes)


# -- refusals ---------------------------------------------------------------


def _bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.run", "--workload",
         "condmat-tips", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _bench(ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout


def test_compile_cache_stays_in_the_checkout(tmp_path):
    """A cache directory named by the environment is not used: importing
    the program compiles, so the checkout's is set before that."""
    co, elsewhere = tmp_path / "checkout", tmp_path / "elsewhere"
    shutil.copytree(ROOT / "src", co / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(HERE, co / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", co)
    elsewhere.mkdir()
    code = (
        "from benchmarks.chip import run as h\n"
        "plan = h.cell_plan(h.load_spec(), 'condmat-tips')\n"
        f"plan = dict(plan, config=dict(plan['config'], **{TINY!r}))\n"
        "res, _ = h.run_cell(plan, 5, 0.0, False, require_accelerator=False)\n"
        "assert res['correct']\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="",
               JAX_COMPILATION_CACHE_DIR=str(elsewhere))
    p = subprocess.run([sys.executable, "-c", code], cwd=co, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert any((co / ".jax_cache").iterdir())
    assert not any(elsewhere.iterdir())


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "metrics" not in p.stdout
