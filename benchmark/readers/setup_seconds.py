"""Process start to window start: imports, inputs, reference, the checked
warm-up job (which loads or compiles every program)."""


def read(run, args):
    return run.setup_seconds
