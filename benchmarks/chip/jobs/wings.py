"""Job kind ``wings``: a wing decomposition from the raw graph.

One job is ``peel_wings(g, **args)``: the program counts the per-edge
butterflies (int64 under global x64), then peels edges. The comparison
is exact: the wing numbers that differ from the reference's (the whole
edge count on a shape mismatch) and the distance between the program's
``sub_rounds`` and the reference's sub-rounds, each with the limit 0.
The wing numbers are peeled from the per-edge counts, so a wrong count
shows in them.

The guarantee is the exact wing number of every edge, peeled from
exact int64 per-edge counts. On the ``condmat`` graph (``graph_seed``
0, any ``--seed``) the reference reads 70,751 butterflies, 18,811 edges
in one or more, a largest per-edge count of 1,312, 202 sub-rounds in 7
range rounds, a largest wing number of 59 (41 distinct), and 11,479,163
of the 11,799,761 candidate triples streamed; the ``condmat-wings``
configuration, that graph, keeps these readings under ``generated``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference_wings import wings_reference

LIMITS = {"wings_wrong": 0, "subrounds_gap": 0}
# the control: the reference peeled from per-edge counts rounded to
# bfloat16 (rounding each sub-round's decrements instead changes no wing
# number on condmat: none reaches 256)
CONTROL = "bfloat16-counts"


def run(g, args: dict):
    from repro.core.peel import peel_wings

    return peel_wings(g, **args)


def reference(config: dict, edges: np.ndarray, acc: str = "int64") -> dict:
    return wings_reference(config["n_u"], config["n_v"], edges, acc=acc)


def readings(answer, ref: dict) -> dict:
    """The compared numbers of one answer (a ``PeelResult`` or a
    reference dict)."""
    if isinstance(answer, dict):
        numbers, sub_rounds = answer["numbers"], answer["rounds"]
    else:
        numbers, sub_rounds = answer.numbers, answer.sub_rounds
    numbers, want = np.asarray(numbers), ref["numbers"]
    wrong = (int(want.size) if numbers.shape != want.shape
             else int(np.count_nonzero(numbers != want)))
    return {"wings_wrong": wrong,
            "subrounds_gap": abs(int(sub_rounds) - int(ref["rounds"]))}
