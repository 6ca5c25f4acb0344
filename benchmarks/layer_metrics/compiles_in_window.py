"""dispatch / compile: step traces (`RECOMPILES`) plus backend compiles
(jax.monitoring) between the first timed send and the end of the drain.
Must be 0: every shape is warmed in set-up."""


def read(run):
    return run["compiles_in_window"]
