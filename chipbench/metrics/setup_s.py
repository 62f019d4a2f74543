"""Process start to the first timed request: TPU init, catalogue from
the seed, the program's warmup, the untimed first requests."""


def read(run):
    return run.setup_s
