"""Share of the traced window in which no operation ran on the device."""
from harness.readers import idle_share


def read(run):
    return idle_share(run)
