"""``recover.busy_share.count``: percent of the device's busy time in
the self time of the count program's ``recover`` scope (each lane's
wedge found from the wedge offsets)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.busy_share(run, "recover")
