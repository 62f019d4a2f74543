"""Request pipeline: requests that missed the result cache per device
batch, over the window (``PipelineStats``)."""


def read(run):
    p = run.pipeline
    if not p or p["n_batches"] <= 0:
        return None
    return (p["n_requests"] - p["n_cached"]) / p["n_batches"]
