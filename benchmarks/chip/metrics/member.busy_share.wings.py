"""``member.busy_share.wings``: percent of the device's busy time in the
self time of the ``member`` scope over a wing decomposition: each
streamed triple's edge-membership search (a binary search of ``v2`` in
``N(other)`` and the reads of what it finds). None where the program
names no such scope."""
from benchmarks.chip import scopes


def read(run):
    if "member" not in (scopes.device_scopes() or ()):
        return None
    return scopes.busy_share(run, "member")
