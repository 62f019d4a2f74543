"""Server, catalogue, registry: XLA executables compiled or loaded from
the persistent cache inside the window (every jit cache miss, engine
executors and the small host-side helpers alike). Set-up has by then
run every batch size the cell sends, beyond what the program's own
``warmup`` runs; ``compiles_after_warmup`` counts what that added."""


def read(run):
    return float(run.compiles)
