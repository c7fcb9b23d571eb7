"""``device_idle.decomp``: percent of the traced window in which no
operation ran on the device, over the tip decompositions."""


def read(run):
    return run.trace.idle_pct()
