"""``recover.busy_share.decomp``: percent of the device's busy time in
the self time of the ``recover`` scope over a tip decomposition: the
peel loop's frontier searches (level 1 and the tile stream's level 2)
and its count's wedge recovery."""
from benchmarks.chip import scopes


def read(run):
    return scopes.busy_share(run, "recover")
