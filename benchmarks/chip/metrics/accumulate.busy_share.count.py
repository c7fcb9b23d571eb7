"""``accumulate.busy_share.count``: percent of the device's busy time in
the self time of the count program's ``accumulate`` scope (the per-lane
scatter-adds into the total, per-vertex and per-edge counts)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.busy_share(run, "accumulate")
