"""CPU self-check of the ``bucket_passes.decomp`` reader on synthetic
trace events: ``python -m pytest benchmarks/chip``."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from benchmarks.chip import run as harness  # noqa: E402
from benchmarks.chip import trace as trace_mod  # noqa: E402

U = 100_000  # ns: one step of the synthetic timeline is 0.1 ms


def _run(jobs, passes_a_job, kernel="bucket_update"):
    """``jobs`` jobs of a peel program: a while enclosing, per pass, a
    fusion and one event of ``kernel``; one more kernel event falls
    after the window's last job."""
    host, device = [], []
    for j in range(jobs):
        t = j * 2000
        host += [["bench.job", t * U, 1000 * U],
                 ["repro.launch._peel_wings_device", (t + 10) * U, 5 * U],
                 ["repro.fetch", (t + 15) * U, 900 * U]]
        device.append(["%while.3 = (s32[]) while(...)", (t + 20) * U,
                       800 * U])
        for p in range(passes_a_job):
            s = t + 30 + 10 * p
            device += [["%fusion.7 = s32[8] fusion(...)", s * U, 4 * U],
                       [f"%{kernel}.8 = (s32[1,512]) custom-call(...)",
                        (s + 5) * U, 3 * U]]
    device.append(["%bucket_update.8 = (s32[1,512]) custom-call(...)",
                   (jobs * 2000 + 100) * U, 3 * U])
    run = harness.Run("TPU v5 lite")
    run.trace = trace_mod.Reduced.from_events([device], host, "bench.job")
    run.jobs = jobs
    return run


def _read(run):
    return harness.load_module("metrics", "bucket_passes.decomp").read(run)


@pytest.mark.parametrize("jobs,passes_a_job", [(1, 5), (3, 2), (2, 40)])
def test_counts_kernel_events_a_job(jobs, passes_a_job):
    assert _read(_run(jobs, passes_a_job)) == pytest.approx(passes_a_job)


def test_silent_without_the_kernel():
    assert _read(_run(2, 4, kernel="bucket_min")) is None
    assert _read(_run(2, 0)) is None


def test_cells_report_it():
    spec = harness.load_spec()
    for cell in ("condmat-tips", "condmat-wings"):
        plan = harness.cell_plan(spec, cell)
        assert "bucket_passes.decomp" in {m["name"] for m in plan["per_layer"]}
    plan = harness.cell_plan(spec, "github-count-cacheopt")
    assert "bucket_passes.decomp" not in {m["name"]
                                          for m in plan["per_layer"]}
