"""Shared benchmark plumbing: graphs, timing, CSV output."""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)  # exact large butterfly counts

from repro.data.graphs import powerlaw_bipartite, random_bipartite  # noqa: E402
from repro.launch.cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

# KONECT-calibrated synthetic stand-ins (paper Table 1 datasets are not
# shipped offline; sizes scaled to CPU-container budgets, heavy tails
# preserved). name -> constructor
BENCH_GRAPHS: Dict[str, Callable] = {
    "pl_small": lambda: powerlaw_bipartite(2_000, 1_500, 12_000, seed=1),
    "pl_medium": lambda: powerlaw_bipartite(20_000, 15_000, 120_000, seed=2),
    "pl_skewed": lambda: powerlaw_bipartite(
        4_000, 50_000, 150_000, alpha_u=1.9, alpha_v=2.4, seed=3
    ),
    "uniform": lambda: random_bipartite(30_000, 30_000, 150_000, seed=4),
}


def timeit(fn: Callable, repeats: int = 3) -> float:
    fn()  # warmup + compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}")
