"""``count_s``: window seconds over the count jobs completed in it. The
window ends when its last job ends, so every second of it is a job's."""


def read(run):
    return run.window_s / run.jobs if run.jobs else None
