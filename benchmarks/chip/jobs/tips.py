"""Job kind ``tips``: a tip decomposition from the raw graph.

One job is ``peel_tips(g, **args)``: the program counts the per-vertex
butterflies of the side it picks (int64 under global x64), then peels.
The comparison is exact: the tip numbers that differ from the
reference's, and whether the peeled side differs, each with the limit 0.
The tip numbers are peeled from the per-vertex counts, so a wrong count
shows in them.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import tips_reference

LIMITS = {"tips_wrong": 0, "side_wrong": 0}
# the control: the reference with each round's decrements rounded to
# bfloat16, as a one-hot matrix-unit contraction without 8-bit limbs gives
CONTROL = "bfloat16"


def run(g, args: dict):
    from repro.core.peel import peel_tips

    return peel_tips(g, **args)


def reference(config: dict, edges: np.ndarray, acc: str = "int64") -> dict:
    return tips_reference(config["n_u"], config["n_v"], edges, acc=acc)


def readings(answer, ref: dict) -> dict:
    """The compared numbers of one answer (a ``PeelResult`` or a
    reference dict)."""
    side = answer["side"] if isinstance(answer, dict) else answer.side
    numbers = np.asarray(
        answer["numbers"] if isinstance(answer, dict) else answer.numbers)
    want = ref["numbers"]
    wrong = (int(want.size) if numbers.shape != want.shape
             else int(np.count_nonzero(numbers != want)))
    return {"tips_wrong": wrong, "side_wrong": int(side != ref["side"])}
