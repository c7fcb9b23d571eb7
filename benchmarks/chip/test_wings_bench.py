"""CPU self-check of the wing cell: ``python -m pytest benchmarks/chip``.

The wing reference against the program's dense wing oracle; the wing
readings that the cell's configuration, the condmat graph, records; the
cell's job through the harness at a tiny size; the lower-precision control and a fault planted
in the program, which the comparison has to catch; the tiles the
``tile_us.wings`` reader counts against those the loop runs.
"""
from __future__ import annotations

import json
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

from benchmarks.chip import graphs  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402
from benchmarks.chip import scopes  # noqa: E402
from benchmarks.chip import trace as trace_mod  # noqa: E402
from benchmarks.chip.jobs import wings  # noqa: E402
from benchmarks.chip.reference_wings import wings_reference  # noqa: E402

CELL = "condmat-wings"
TINY = {"n_u": 90, "n_v": 120, "m": 700, "alpha_u": 2.1, "alpha_v": 2.1,
        "graph_seed": 3}


def _graph(seed, n_u=60, n_v=80, m=500):
    from repro.core.graph import BipartiteGraph

    e = graphs.relabel(graphs.powerlaw_edges(n_u, n_v, m, 2.1, 2.1, seed),
                       n_u, n_v, seed + 100)
    return BipartiteGraph(n_u, n_v, e, on_duplicate="raise"), e


def _tiny_plan(config: dict = TINY) -> dict:
    plan = harness.cell_plan(harness.load_spec(), CELL)
    return dict(plan, config=dict(plan["config"], **config))


def _range_rounds(kappas):
    rounds, hi = 0, 0
    for k in kappas:
        if k >= hi:
            rounds, hi = rounds + 1, 1 << int(k).bit_length()
    return rounds


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_wings_reference_matches_oracle(seed):
    from repro.core import oracle

    g, e = _graph(seed)
    ref = wings_reference(g.n_u, g.n_v, e)
    want, mins = oracle.wing_numbers(g)
    assert np.array_equal(ref["numbers"], want)
    assert ref["rounds"] == len(mins)
    assert np.array_equal(ref["counts"], oracle.per_edge_counts(g))
    deg = np.bincount(e[:, 0], minlength=g.n_u)
    triples = [np.minimum(deg[u1], deg[e[e[:, 1] == v1, 0]]).sum()
               for u1, v1 in e]
    assert np.array_equal(ref["triples"], triples)
    assert wings.readings(ref, ref) == {"wings_wrong": 0, "subrounds_gap": 0}


@pytest.fixture(scope="module")
def condmat():
    plan = harness.cell_plan(harness.load_spec(), CELL)
    config = plan["config"]
    e = graphs.build_edges(config, 2**33 + 5)
    return plan, e, wings_reference(config["n_u"], config["n_v"], e)


def test_condmat_wing_readings(condmat):
    """The cell's configuration is the condmat graph, and holds its wing
    readings; the control fails there."""
    plan, e, ref = condmat
    config, gen = plan["config"], plan["config"]["generated"]
    with open(HERE / "configs" / "condmat.json") as f:
        graph = json.load(f)
    keys = ("n_u", "n_v", "m", "alpha_u", "alpha_v", "graph_seed")
    assert {k: config[k] for k in keys} == {k: graph[k] for k in keys}
    assert ref["counts"].sum() == 4 * graph["generated"]["butterflies"]
    assert ref["counts"].sum() == 4 * gen["butterflies"]
    assert (ref["counts"] > 0).sum() == gen["edges_in_butterflies"]
    assert ref["counts"].max() == gen["max_edge_count"]
    assert ref["rounds"] == gen["wing_subrounds"]
    kappas = np.zeros(ref["rounds"], np.int64)
    kappas[ref["step"]] = ref["numbers"]
    assert _range_rounds(kappas) == gen["wing_range_rounds"]
    assert ref["numbers"].max() == gen["max_wing"]
    assert np.unique(ref["numbers"]).size == gen["distinct_wings"]
    assert ref["triples"].sum() == gen["candidate_triples"]
    streamed = np.bincount(ref["step"], weights=ref["triples"])[:-1].sum()
    assert streamed == gen["triples_streamed"]
    low = wings_reference(config["n_u"], config["n_v"], e, acc=wings.CONTROL)
    got = wings.readings(low, ref)
    assert any(v > wings.LIMITS[k] for k, v in got.items()), got


def test_cell_runs_through_the_harness(tmp_path):
    plan = _tiny_plan()
    result, run = harness.run_cell(plan, seed=2**33 + 1, seconds=0.0,
                                   trace=False, require_accelerator=False,
                                   cache_dir=tmp_path)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "decomp_s"}
    assert set(result["checks"]) == set(wings.LIMITS)
    assert run.window_compiles == 0


def test_off_by_one_decrement_is_not_correct(monkeypatch, tmp_path):
    from repro.core import peel

    orig = peel._apply_decrements

    def faulty(b, alive, tgt, dec, *rest):
        return orig(b, alive, tgt, dec.at[0].add(1), *rest)

    jax.clear_caches()
    monkeypatch.setattr(peel, "_apply_decrements", faulty)
    try:
        result, _run = harness.run_cell(
            _tiny_plan(), seed=11, seconds=0.0, trace=False,
            require_accelerator=False, cache_dir=tmp_path)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not result["correct"], result["checks"]


def test_tile_count_is_the_loops(monkeypatch):
    """``tile_us.wings`` divides by the tiles the wing loop really
    streams: counted here by a callback in each tile's subtract."""
    from repro.core import peel
    from repro.core.pipeline import record_programs

    g, e = _graph(5, n_u=150, n_v=120, m=1200)
    ref = wings_reference(g.n_u, g.n_v, e)
    ran = []
    orig = peel._subtract_edge_groups

    def counted(*args, **kwargs):
        jax.debug.callback(lambda: ran.append(1))
        return orig(*args, **kwargs)

    jax.clear_caches()
    monkeypatch.setattr(peel, "_subtract_edge_groups", counted)
    try:
        with record_programs() as programs:
            res = peel.peel_wings(g, engine="device", peel_mode="range",
                                  tile_budget=128)
        jax.effects_barrier()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.array_equal(res.numbers, ref["numbers"])
    programs = [(p.__name__, a, k) for p, a, k in programs]
    tiles = harness.load_module("metrics", "tile_us.wings").streamed_tiles(
        programs, ref)
    assert tiles == len(ran) > ref["rounds"]


U = 100_000  # ns: one step of the synthetic timeline is 0.1 ms


def _wing_run():
    """A synthetic traced window of two jobs: each launches the wing
    program (a while enclosing two fusions) and fetches once."""
    host, device = [], []
    for t in (0, 2000):
        host += [["bench.job", t * U, 1000 * U],
                 ["repro.launch._peel_wings_device", (t + 10) * U, 5 * U],
                 ["repro.fetch", (t + 15) * U, 600 * U]]
        device += [["%while.1 = (s32[]) while(...)", (t + 20) * U, 400 * U],
                   ["%fusion.1 = s32[8] fusion(...)", (t + 30) * U, 100 * U],
                   ["%fusion.2 = s32[8] fusion(...)", (t + 200) * U, 50 * U]]
    run = harness.Run("TPU v5 lite")
    run.trace = trace_mod.Reduced.from_events([device], host, "bench.job")
    work2 = np.array([3, 5, 4, 0, 9])
    args = (None,) * 10 + (work2,)
    run.programs = [("_peel_wings_device", args, {"tile_cap": 4})]
    run.jobs = 2
    run.reference = {"step": np.array([0, 0, 1, 2, 2]), "rounds": 3,
                     "triples": work2.copy()}
    run._program_scopes = {"_peel_wings_device": {
        "while.1": ("select", "calls"), "fusion.1": ("member", "op_name"),
        "fusion.2": ("recover", "op_name")}}
    return run


def test_wing_readers_on_a_synthetic_window(monkeypatch):
    run = _wing_run()
    monkeypatch.setattr(scopes, "device_scopes",
                        lambda: ("recover", "member", "select"))
    read = {m: harness.load_module("metrics", m).read
            for m in ("tile_us.wings", "member.busy_share.wings",
                      "recover.busy_share.decomp")}
    # sub-rounds 0 and 1 stream ceil(8 / 4) + ceil(4 / 4) tiles; the
    # last sub-round streams none
    assert read["tile_us.wings"](run) == pytest.approx(400 * U * 1e-3 / 3)
    busy = run.trace.busy_s
    assert read["member.busy_share.wings"](run) == pytest.approx(
        100 * 2 * 100 * U * 1e-9 / busy)
    assert read["recover.busy_share.decomp"](run) == pytest.approx(
        100 * 2 * 50 * U * 1e-9 / busy)
    monkeypatch.setattr(scopes, "device_scopes",
                        lambda: ("recover", "select"))
    assert read["member.busy_share.wings"](run) is None
    assert harness.load_module("metrics", "host_syncs.decomp").read(run) == 1


def test_tile_reader_refuses_another_argument():
    """An argument at ``WORK2`` that is not the per-edge candidate
    triples (another length, or other values) gives no reading."""
    run = _wing_run()
    reader = harness.load_module("metrics", "tile_us.wings")
    name, args, kw = run.programs[0]
    for other in (np.array([3, 5, 4, 0]), np.array([3, 5, 4, 1, 9])):
        bad = args[:reader.WORK2] + (other,)
        assert reader.streamed_tiles([(name, bad, kw)], run.reference) is None
    assert reader.streamed_tiles([(name, args[:5], kw)], run.reference) is None
    assert reader.streamed_tiles(run.programs, run.reference) == 3


def test_cell_reads_the_decomposition_metrics():
    """The wing cell is on the decomposition metrics' lists, beside its
    own two readers."""
    plan = harness.cell_plan(harness.load_spec(), CELL)
    assert {m["name"] for m in plan["end_to_end"]} == {"setup_s", "decomp_s"}
    assert {m["name"] for m in plan["per_layer"]} == {
        "compile_s", "device_idle.decomp", "bucket_update_roofline",
        "host_prep_s.decomp", "host_syncs.decomp",
        "recover.busy_share.decomp", "member.busy_share.wings",
        "tile_us.wings"}
