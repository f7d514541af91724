"""Roofline share of paged flash-decode over the int4 KV cache (kernels/decode_attention.py _paged_decode_kernel)."""
from harness.layers import attn_roofline


def read(run):
    return attn_roofline(run)
