"""``host_lead_s.count``: seconds from the start of a job's host span to
its first device operation, averaged over the window's jobs: the host's
ranking, CSR and planning ahead of the count program."""


def read(run):
    return run.trace.host_lead_s()
