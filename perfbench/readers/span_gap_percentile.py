"""Per request, the time from the beginning of its ``start`` span to the
beginning of its ``end`` span (matched by the attribute ``key``), over the
requests whose ``start`` lies inside the window: the ``q``-th percentile, ms."""

from .. import stats


def read(record, start, end, key, q):
    w0, w1 = record["window"]
    first, later = {}, {}
    for e in record["spans"]:
        if e["kind"] != "B" or key not in e["attrs"]:
            continue
        if e["name"] == start and w0 <= e["ts"] < w1:
            first.setdefault(e["attrs"][key], e["ts"])
        elif e["name"] == end:
            later.setdefault(e["attrs"][key], e["ts"])
    return stats.percentile(
        [(later[k] - t) * 1e3 for k, t in first.items() if k in later], q)
