"""compile: XLA backend-compile seconds during set-up (jax.monitoring); a
run that finds every program in the persistent cache reads the seconds the
cache reads took."""


def read(run):
    c = run["compile_setup"]
    return c["backend_compile_s"] + c["cache_read_s"]
