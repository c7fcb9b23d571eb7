"""Distributed counting engine on a multi-device host mesh.

These run in a subprocess so the 8-device XLA flag doesn't leak into
the rest of the suite (smoke tests must see 1 device)."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8) -> str:
    """Dispatch a payload through the resilience layer's per-device
    worker launcher: bounded retry + per-attempt timeout, DeviceLost on
    exhaustion (carrying the stderr tail the old assert used to show)."""
    from repro.core.distributed import launch_device_worker

    return launch_device_worker(code, devices=devices, retries=1)


@pytest.mark.slow
def test_distributed_count_matches_oracle_8dev():
    code = """
import numpy as np, jax
from jax.sharding import AxisType
from repro.core import BipartiteGraph
from repro.core.oracle import global_count, per_vertex_counts
from repro.core.distributed import distributed_count

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
rng = np.random.default_rng(0)
e = np.stack([rng.integers(0, 50, 300), rng.integers(0, 40, 300)], axis=1)
g = BipartiteGraph(50, 40, e)
got, rg = distributed_count(g, mesh, mode="global")
assert int(got) == global_count(g), (int(got), global_count(g))
got_v, rg = distributed_count(g, mesh, mode="vertex")
pu, pv = per_vertex_counts(g)
gv = np.asarray(got_v)
assert np.array_equal(gv[rg.rank_of_u], pu)
assert np.array_equal(gv[rg.rank_of_v], pv)
print("DIST_OK")
"""
    assert "DIST_OK" in run_sub(code)


