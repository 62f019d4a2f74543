"""Server, catalogue, registry: XLA executables compiled or loaded from
the persistent cache in set-up after the program's own ``warmup``, by
the first requests of every batch size the cell sends. The program's
``warmup`` leaves these for serving; the harness runs them before the
window, so that nothing compiles inside it."""


def read(run):
    return float(run.compiles_after_warmup)
