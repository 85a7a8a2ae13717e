"""Device's idle share of the traced window (%): 1 minus the union of
the device's op intervals over the window, averaged over the chips.
Moves ``itl_p95_ms``."""

from chipbench import tracefile


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    lo, hi = tr.window
    busy = [tracefile.busy_ns(tr.ops[c], tr.window) for c in tr.chips]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
