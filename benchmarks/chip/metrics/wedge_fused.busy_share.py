"""``wedge_fused.busy_share``: percent of the device's busy time spent in
the ``wedge_fused`` kernel."""


def read(run):
    k = run.trace.kernel_s("wedge_fused")
    if not k or not run.trace.busy_s:
        return None
    return 100.0 * k / run.trace.busy_s
