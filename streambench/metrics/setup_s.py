"""Set-up: from the start of the process to the first due chunk (imports,
record pool, Pipeline build with its attestation, warm-up windows and any
compiles or cache loads)."""


def read(run):
    return run.t_open - run.t_process
