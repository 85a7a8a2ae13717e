"""Reductions shared by the per-layer metric readers in ``metrics/``.

Each takes a :class:`chipbench.cell.Run` of a traced run and returns
``None`` where it finds nothing to read: a share of a peak or of a
roofline is never reported as 0 for want of data.
"""

from __future__ import annotations

from typing import Optional

from . import tracefile

STEP_PROGRAM = "_unified_step"     # the executor's jitted step
PAGED_KERNEL = "paged_attention"   # the Pallas kernel's stable name


def step_mfu(run) -> Optional[float]:
    """Model operations of the window's steps (from their spans) over the
    device time of the step program times the chip's bf16 peak, in %."""
    if run.trace is None:
        return None
    steps = run.steps_in_window()
    dev = sum(tracefile.time_of(run.trace.modules.get(c, []),
                                STEP_PROGRAM)[0] for c in run.trace.chips)
    if not steps or dev <= 0:
        return None
    work = sum(run.model.step_flops(s[4]) for s in steps)
    return 100.0 * work / (dev / 1e9 * run.peaks["bf16_flops_per_s"])


def kernel_roofline(run) -> Optional[float]:
    """Least time of the paged-attention calls over their device time,
    in %.  A call's least time is the larger of its operations over the
    peak rate and its least bytes over the peak bandwidth."""
    if run.trace is None:
        return None
    steps = run.steps_in_window()
    dev, n = 0.0, 0
    for c in run.trace.chips:
        t, k = tracefile.time_of(run.trace.ops[c], PAGED_KERNEL)
        dev, n = dev + t, n + k
    if not steps or n == 0 or dev <= 0:
        return None
    m, pk = run.model, run.peaks
    least = m.n_layers * sum(
        max(m.attn_flops(s[4]) / pk["bf16_flops_per_s"],
            m.kernel_bytes(s[4]) / pk["hbm_bytes_per_s"]) for s in steps)
    return 100.0 * least / (dev / 1e9)
