"""``bucket_update_roofline``: the least time of a decomposition's real
count decrements and bucket moves (from the reference's peel of the same
graph), times the jobs traced, over the ``bucket_update`` kernel's time
in the trace, in percent."""
from benchmarks.chip import roofline


def read(run):
    ref = run.reference
    ops, nbytes = roofline.bucket_update_work(
        ref["decrements"] * run.jobs, ref["moves"] * run.jobs)
    return roofline.share_pct(run.device_kind, ops, nbytes,
                              run.trace.kernel_s("bucket_update"))
