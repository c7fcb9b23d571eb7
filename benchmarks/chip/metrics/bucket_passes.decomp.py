"""``bucket_passes.decomp``: ``bucket_update`` kernel passes a job, the
device trace's events of that kernel in the window (one an execution)
over the jobs traced. Each pass sweeps the whole count array; the round
loop needs the min and occupancy of one a sub-round that streams any
update. None where the window holds no such event."""
from benchmarks.chip import trace

KERNEL = "bucket_update"


def passes(events: list) -> int:
    """Events of the kernel among ``[name, start_ns, duration_ns]``."""
    return sum(1 for name, _s, _d in events
               if trace.base_name(name) == KERNEL)


def read(run):
    n = passes(run.trace._window(run.trace.device[0]))
    return n / run.jobs if n else None
