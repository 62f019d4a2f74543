"""Exact answers completed in the window over the window's seconds."""


def read(run):
    return run.n_exact / run.window_s
