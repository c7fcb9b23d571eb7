"""``host_prep_s.count``: seconds a count job spends in the program's
host preprocessing, the outermost ``repro.rank``, ``repro.preprocess``
and ``repro.plan`` spans, averaged over the window's jobs."""
from benchmarks.chip import scopes


def read(run):
    return scopes.host_prep_s(run)
