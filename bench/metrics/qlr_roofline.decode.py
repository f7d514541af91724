"""Roofline share of the fused Q+LR decode matmul (kernels/mxint_matmul.py _fused_kernel), doc-QA cells."""
from harness.layers import qlr_roofline


def read(run):
    return qlr_roofline(run)
