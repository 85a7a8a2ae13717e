"""End-to-end metrics from the clients' records (see ``loadgen.py``).

All times are on the load generator's clock, in seconds from its start;
the window is ``[w0, w1)``.

* TTFT of a request sent in the window: from its due time to its first
  token event.  A failed request (refused, error event, too few tokens,
  never finished) counts as missing: it is given the time from its due
  time to the end of the run, which ranks it above every request that
  answered.
* Inter-token gap: every gap between consecutive token events of any
  request whose later event falls in the window.
* Output rate: token events received in the window over its length.

Percentiles are nearest-rank over every sample, with no smoothing or
averaging of pieces, so one stall in the window moves the tail.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def failed(rec: dict) -> bool:
    """Refused, ended in an error event or never, or short of tokens."""
    return (rec["terminal"] != "finished"
            or len(rec["tokens"]) != rec["max_new_tokens"])


def in_window(t: float, window: Sequence[float]) -> bool:
    return window[0] <= t < window[1]


def sent_in_window(records: List[dict], window) -> List[dict]:
    return [r for r in records if in_window(r["due"], window)]


def ttfts(records: List[dict], window, end: float) -> List[float]:
    """TTFT of every request sent in the window; a failed one waited
    until ``end``, the end of the run."""
    return [end - r["due"] if failed(r) or not r["times"]
            else r["times"][0] - r["due"]
            for r in sent_in_window(records, window)]


def gaps(records: List[dict], window) -> List[float]:
    """Every inter-token gap whose later token arrived in the window."""
    out: List[float] = []
    for r in records:
        ts = r["times"]
        out += [b - a for a, b in zip(ts, ts[1:]) if in_window(b, window)]
    return out


def tokens_in_window(records: List[dict], window) -> int:
    return sum(1 for r in records for t in r["times"]
               if in_window(t, window))


def lateness(records: List[dict], window) -> Dict[str, float]:
    """How late the generator sent the window's requests (s)."""
    late = sorted(r["sent"] - r["due"]
                  for r in sent_in_window(records, window)
                  if r["sent"] is not None)
    if not late:
        return {"late_p99_ms": 0.0, "late_max_ms": 0.0}
    return {"late_p99_ms": percentile(late, 99) * 1e3,
            "late_max_ms": late[-1] * 1e3}


def end_to_end(records: List[dict], window, end: float,
               wanted: Sequence[str]) -> Dict[str, Optional[float]]:
    """The end-to-end metrics named in ``wanted`` (those of the client:
    ``ttft_p95_ms``, ``itl_p95_ms``, ``output_tok_s``)."""
    out: Dict[str, Optional[float]] = {}
    if "ttft_p95_ms" in wanted:
        out["ttft_p95_ms"] = percentile(ttfts(records, window, end),
                                        95) * 1e3
    if "itl_p95_ms" in wanted:
        out["itl_p95_ms"] = percentile(gaps(records, window), 95) * 1e3
    if "output_tok_s" in wanted:
        out["output_tok_s"] = (tokens_in_window(records, window)
                               / (window[1] - window[0]))
    return out
