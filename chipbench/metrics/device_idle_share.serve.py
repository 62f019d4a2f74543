"""Device: share of the traced window with no op on the chip, in %."""

from chipbench.readers import idle_share


def read(run):
    return idle_share(run)
