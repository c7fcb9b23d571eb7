"""The device peel loops' batched decrease-key: every decrement of a
sub-round is an int32 scatter-add except the last update chunk of its
last tile, which goes through ``bucket_update``. So the kernel runs
once per sub-round that streams anything, and never for an earlier tile
or chunk. Checked on the reference path and with the Pallas kernel in
interpret mode: tip and wing numbers bitwise-equal to the host engine
and a recompute-from-scratch oracle in both peel modes, and the passes
counted by a callback in ``ops.bucket_update``."""
import jax
import numpy as np
import pytest

from repro.core import BipartiteGraph
from repro.core import peel as pm
from repro.core.oracle import per_edge_counts, per_vertex_counts
from repro.core.pipeline import record_programs
from repro.kernels import ops


def rand_graph(nu, nv, m, seed):
    rng = np.random.default_rng(seed)
    e = np.stack([rng.integers(0, nu, m), rng.integers(0, nv, m)], axis=1)
    return BipartiteGraph(nu, nv, e)


# decomposition, graph, tile_budget: many tiles a sub-round (a budget
# below the largest single expansion), or batches wider than
# MAX_UPDATE_CAP (8,192-lane tip and stored-wedge tiles, 3 x 2,048-lane
# wing tiles), so the kernel sees only their last chunk
CASES = {
    "tips-many-tiles": ("tips", (80, 30, 300, 1), 1),
    "tips-chunked": ("tips", (40, 30, 500, 2), 8192),
    "stored-chunked": ("stored", (40, 30, 500, 2), 8192),
    "wings-many-tiles": ("wings", (14, 11, 60, 5), 128),
    "wings-chunked": ("wings", (24, 20, 170, 6), 2048),
}


def _oracle(g, decomp):
    """Exact peel from recomputed counts: the numbers, and each
    sub-round's peel set and whether anything stays alive after it."""
    n = g.m if decomp == "wings" else g.n_u
    alive = np.ones(n, bool)
    numbers = np.zeros(n, np.int64)
    kappa, rounds = 0, []
    while alive.any():
        if decomp != "wings":
            sub = g.edges[alive[g.edges[:, 0]]]
            cur = per_vertex_counts(BipartiteGraph(g.n_u, g.n_v, sub))[0]
        else:
            cur = np.zeros(n, np.int64)
            cur[alive] = per_edge_counts(
                BipartiteGraph(g.n_u, g.n_v, g.edges[alive]))
        cur = np.where(alive, cur, np.iinfo(np.int64).max)
        kappa = max(kappa, int(cur.min()))
        peel = alive & (cur <= kappa)
        numbers[peel] = kappa
        alive &= ~peel
        rounds.append((peel, alive.any()))
    return numbers, rounds


def _expected_passes(g, decomp, rounds):
    """Sub-rounds that apply any update batch: those that leave
    something alive and stream a non-empty id space (every peeled
    vertex of positive degree or with stored wedges, or any peeled
    edge, streams some). Also the largest space streamed."""
    off, nbr, _uid = pm._csr(g)
    if decomp == "tips":
        work = pm._level2_totals(off, nbr, 0, g.n_u)
    elif decomp == "stored":
        work = np.diff(pm._stored_wedge_csr(g, 0)[0])
    else:
        work = pm._wing_work_totals(g, off, nbr)[3]
    totals = [int(work[peel].sum()) for peel, _ in rounds]
    streamed = [t for (_peel, more), t in zip(rounds, totals) if more]
    return sum(1 for t in streamed if t > 0), max(streamed)


@pytest.mark.parametrize("kernel", ["reference", "interpret"])
@pytest.mark.parametrize("peel_mode", ["exact", "range"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_one_bucket_pass_per_sub_round(case, peel_mode, kernel,
                                       monkeypatch):
    decomp, shape, budget = CASES[case]
    g = rand_graph(*shape)
    want, rounds = _oracle(g, decomp)
    passes, widths = [], []
    real = ops.bucket_update

    def counted(counts, alive, idx, dec, use_pallas=False, interpret=None):
        widths.append((idx.shape[0], use_pallas))
        jax.debug.callback(lambda: passes.append(1))
        return real(counts, alive, idx, dec, use_pallas=use_pallas,
                    interpret=interpret)

    jax.clear_caches()  # the loop is traced again, through the wrapper
    monkeypatch.setattr(ops, "bucket_update", counted)
    if kernel == "interpret":  # the kernel path, interpreted off the chip
        monkeypatch.setattr(ops, "interpret_default", lambda: False)
    peel = {"tips": pm.peel_tips, "stored": pm.peel_tips_stored}
    kw = dict(engine="device", peel_mode=peel_mode, tile_budget=budget)
    try:
        with record_programs() as programs:
            if decomp == "wings":
                got = pm.peel_wings(g, **kw)
            else:
                got = peel[decomp](g, side=0, **kw)
        jax.effects_barrier()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    host = (pm.peel_wings(g, peel_mode=peel_mode) if decomp == "wings"
            else peel[decomp](g, side=0, peel_mode=peel_mode))
    assert got.report.final_rung == "device"
    assert np.array_equal(got.numbers, want)
    assert np.array_equal(got.numbers, host.numbers)
    assert got.rounds == host.rounds and got.sub_rounds == len(rounds)
    assert np.array_equal(got.round_sizes, host.round_sizes)

    expected, widest = _expected_passes(g, decomp, rounds)
    assert len(passes) == expected, (len(passes), expected)
    assert {w for w, _p in widths} and all(
        w <= ops.MAX_UPDATE_CAP and p == (kernel == "interpret")
        for w, p in widths)
    (_fn, _args, loop_kw), = [p for p in programs
                              if p[0].__name__.startswith("_peel_")]
    lanes = loop_kw["tile_cap"] * (3 if decomp == "wings" else 1)
    if case.endswith("chunked"):  # the kernel saw only the last chunk
        assert lanes > ops.MAX_UPDATE_CAP
        assert {w for w, _p in widths} == {
            lanes - (lanes - 1) // ops.MAX_UPDATE_CAP * ops.MAX_UPDATE_CAP}
    else:  # some sub-round streamed several tiles
        assert widest > loop_kw["tile_cap"]
