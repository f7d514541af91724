"""Share of the traced window in which no operation ran on the device, doc-QA cells."""
from harness.layers import idle_share


def read(run):
    return idle_share(run)
