"""``device_idle.count``: percent of the traced window in which no
operation ran on the device, over the count cells' jobs."""


def read(run):
    return run.trace.idle_pct()
