"""The lower-precision control of a cell, which the comparison must fail.

    python3 -m benchmarks.chip.control --workload <cell> --seeds 1 2 3

For each seed: the cell's graph, the job kind's plain reference, and the
same reference in the job's ``CONTROL`` arithmetic put in the program's
place. Prints each number compared beside its limit, one JSON line per
seed; the control has to come out not correct, by the harness's own
comparison, on every seed. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys

from benchmarks.chip import checks, graphs
from benchmarks.chip.run import cell_plan, load_module, load_spec


def control_readings(plan: dict, seed: int) -> dict:
    job = load_module("jobs", plan["traffic"]["job"])
    edges = graphs.build_edges(plan["config"], seed)
    ref = job.reference(plan["config"], edges)
    low = job.reference(plan["config"], edges, acc=job.CONTROL)
    compared = checks.compare(job, [low], ref)
    return {"seed": seed, "control": job.CONTROL, "readings": compared,
            "correct": checks.correct(compared)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    plan = cell_plan(load_spec(), args.workload)
    ok = True
    for seed in args.seeds:
        r = control_readings(plan, seed)
        ok &= not r["correct"]
        print(json.dumps(dict(r, workload=args.workload)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
