"""Share of the chip's bf16 peak in the engine's decode step program, doc-QA cells."""
from harness.layers import step_mfu


def read(run):
    return step_mfu(run)
