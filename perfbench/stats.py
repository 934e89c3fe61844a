"""Metric arithmetic over client stamps. Stdlib only; the yardstick's own.

A *request* here is one dict of the load generator's out file::

    {"id": 3, "due": 1012.25, "sent": 1012.2503, "tokens": [1012.31, ...],
     "prompt_len": 96, "new_tokens": 64, "ok": true, "counted": true}

``due``/``sent``/``tokens`` are ``time.monotonic()`` seconds of the child
process. Every latency is timed from ``due``, never from ``sent``: a stall
that delays the generator is charged to the system that caused the stall's
queue, and how late the generator itself ran is its own metric.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default rule), ``q`` in 0..100.
    ``None`` for no values, so that a reader with nothing to read returns
    nothing."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Sequence[float]) -> Optional[float]:
    xs = [float(v) for v in values]
    return sum(xs) / len(xs) if xs else None


def counted(requests: Iterable[Dict]) -> List[Dict]:
    """The requests a run is judged on: those due inside the window."""
    return [r for r in requests if r.get("counted")]


def finished(r: Dict) -> bool:
    return bool(r.get("ok")) and len(r.get("tokens", ())) == r["new_tokens"]


def failed_count(requests: Iterable[Dict]) -> int:
    """Counted requests that errored or did not stream every token inside
    the drain limit."""
    return sum(1 for r in counted(requests) if not finished(r))


def quantity(requests: Iterable[Dict], name: str) -> List[float]:
    """One list of samples in milliseconds over the counted, finished
    requests.

    * ``tpot_ms`` — per request, (last token - first token) / (tokens - 1);
    * ``itl_ms``  — every gap between consecutive streamed tokens, pooled;
    * ``ttft_ms`` — per request, first token - due;
    * ``late_ms`` — per request, sent - due: how late the generator ran.
    """
    rs = [r for r in counted(requests) if finished(r)]
    if name == "late_ms":
        return [(r["sent"] - r["due"]) * 1e3 for r in rs]
    if name == "ttft_ms":
        return [(r["tokens"][0] - r["due"]) * 1e3 for r in rs]
    if name == "tpot_ms":
        return [(r["tokens"][-1] - r["tokens"][0]) * 1e3
                / (len(r["tokens"]) - 1)
                for r in rs if len(r["tokens"]) > 1]
    if name == "itl_ms":
        return [(b - a) * 1e3 for r in rs
                for a, b in zip(r["tokens"], r["tokens"][1:])]
    raise KeyError(f"unknown client quantity {name!r}")


def spread(values: Sequence[float]) -> float:
    """The contract's spread: interquartile distance by
    ``statistics.quantiles(values, n=4)`` as a share of the median."""
    import statistics
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
