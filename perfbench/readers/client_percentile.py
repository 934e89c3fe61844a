"""A percentile of a client-side quantity (``stats.quantity``: tpot_ms,
itl_ms, ttft_ms, late_ms) over the counted requests."""

from .. import stats


def read(record, quantity, q):
    return stats.percentile(stats.quantity(record["requests"], quantity), q)
