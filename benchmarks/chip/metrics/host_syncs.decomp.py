"""``host_syncs.decomp``: device-to-host transfers a tip decomposition
makes, its ``repro.fetch`` spans, averaged over the window's jobs."""
from benchmarks.chip import scopes


def read(run):
    return scopes.host_syncs(run)
