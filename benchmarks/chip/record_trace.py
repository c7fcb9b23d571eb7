"""Record the small trace the CPU self-check reduces.

    python3 -m benchmarks.chip.record_trace  # on a TPU

Runs a small count job through the harness with the profiler on and
writes the window's device operations and the job spans' host thread to
``testdata/count_trace.json.gz``, with what the reduction read from them
then, so that a later change to the reduction shows in the test.
"""
from __future__ import annotations

import gzip
import json
import shutil
import sys
import tempfile

from benchmarks.chip import run as harness
from benchmarks.chip import trace as trace_mod

SMALL = {"n_u": 3000, "n_v": 4000, "m": 20000, "alpha_u": 2.1,
         "alpha_v": 2.1, "graph_seed": 5}
OUT = harness.HERE / "testdata" / "count_trace.json.gz"


def main() -> int:
    plan = harness.cell_plan(harness.load_spec(), "github-count-cacheopt")
    plan = dict(plan, config=dict(plan["config"], **SMALL))
    tdir = tempfile.mkdtemp(prefix="chip-trace-")
    try:
        harness.run_cell(plan, seed=7, seconds=0.5, trace=True, trace_dir=tdir)
        files = sorted(harness.Path(tdir).rglob("*.xplane.pb"))
        device, host = trace_mod.events_from_xspace(str(files[-1]),
                                                    harness.JOB_SPAN)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    red = trace_mod.Reduced.from_events(device, host, harness.JOB_SPAN)
    keep = [e for e in host if e[1] < red.w1 and e[1] + e[2] > red.w0]
    expected = {"window_s": red.window_s, "busy_s": red.busy_s,
                "wedge_fused_s": red.kernel_s("wedge_fused"),
                "host_lead_s": red.host_lead_s(),
                "breakdown": red.breakdown()}
    OUT.parent.mkdir(exist_ok=True)
    with gzip.open(OUT, "wt") as f:
        json.dump({"device": device, "host": keep, "expected": expected}, f)
    print(json.dumps(expected))
    return 0


if __name__ == "__main__":
    sys.exit(main())
