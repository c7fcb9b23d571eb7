"""``compile_s``: seconds JAX spent in set-up compiling programs for the
backend or reading them from the persistent cache (its monitoring
events ``backend_compile_duration`` and ``cache_retrieval_time_sec``)."""


def read(run):
    return run.setup_compile_s
