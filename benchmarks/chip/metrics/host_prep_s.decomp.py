"""``host_prep_s.decomp``: seconds a tip decomposition spends in the
program's host preprocessing, the outermost ``repro.rank``,
``repro.preprocess`` and ``repro.plan`` spans (its count's and its
peel's), averaged over the window's jobs."""
from benchmarks.chip import scopes


def read(run):
    return scopes.host_prep_s(run)
