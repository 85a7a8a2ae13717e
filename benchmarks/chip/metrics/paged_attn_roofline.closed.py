"""Paged-attention kernel's share of its roofline, closed loop (%).

Moves ``output_tok_s``: with the clients always waiting, a faster
kernel completes more tokens."""

from chipbench.layers import kernel_roofline


def read(run):
    return kernel_roofline(run)
