"""``setup_s``: process start to window start (JAX on the chip, the
graph, compiling or loading every program, the warm-up job)."""


def read(run):
    return run.setup_s
