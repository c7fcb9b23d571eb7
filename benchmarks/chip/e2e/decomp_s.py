"""``decomp_s``: window seconds over the tip decompositions completed in
it, each with its own per-vertex count. The window ends when its last
job ends."""


def read(run):
    return run.window_s / run.jobs if run.jobs else None
