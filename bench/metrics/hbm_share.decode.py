"""Share of peak HBM bandwidth the decode step program reaches on weights plus live KV."""
from harness.layers import step_hbm


def read(run):
    return step_hbm(run)
