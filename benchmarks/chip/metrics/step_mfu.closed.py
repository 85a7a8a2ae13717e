"""Whole step's share of the chip's bf16 peak, in a closed-loop cell (%).

Moves ``output_tok_s``: with the clients always waiting, a faster step
completes more tokens."""

from chipbench.layers import step_mfu


def read(run):
    return step_mfu(run)
