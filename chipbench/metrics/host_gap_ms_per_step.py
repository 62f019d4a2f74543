"""Server, catalogue, registry: step wall time minus the naive
executor's device time, per step of the traced window."""


def read(run):
    if run.step_wall_s is None or run.trace is None \
            or len(run.step_wall_s) == 0:
        return None
    gap = float(run.step_wall_s.sum()) - run.trace.executor_s
    return 1e3 * gap / len(run.step_wall_s)
