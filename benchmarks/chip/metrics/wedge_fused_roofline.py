"""``wedge_fused_roofline``: the least time of the real wedges the count
program hands the ``wedge_fused`` kernel, over the kernel's time in the
trace, in percent. The wedges are those of its kernel tiles as launched
(each tile a flat ``[start, end)`` wedge range), times the jobs traced."""
import numpy as np

from benchmarks.chip import roofline

PROGRAM = "run_fused_pallas_program"


def kernel_wedges(programs) -> int:
    """Real wedges in the kernel tiles of one job's count program."""
    total = 0
    for name, args, _kw in programs:
        if name == PROGRAM:
            tiles = np.asarray(args[1]).reshape(-1, 2).astype(np.int64)
            total += int((tiles[:, 1] - tiles[:, 0]).sum())
    return total


def read(run):
    wedges = kernel_wedges(run.programs) * run.jobs
    if not wedges:
        return None
    ops, nbytes = roofline.wedge_fused_work(wedges)
    return roofline.share_pct(run.device_kind, ops, nbytes,
                              run.trace.kernel_s("wedge_fused"))
