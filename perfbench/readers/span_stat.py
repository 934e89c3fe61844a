"""Over the program's spans named ``span`` that began inside the window:
the ``q``-th percentile of their durations in ms, or (with ``attr``) the
mean of one of their attributes."""

from .. import stats


def read(record, span, attr=None, q=50):
    w0, w1 = record["window"]
    begun = {e["span"]: e for e in record["spans"]
             if e["kind"] == "B" and e["name"] == span and w0 <= e["ts"] < w1}
    if attr is not None:
        return stats.mean([e["attrs"][attr] for e in begun.values()
                           if attr in e["attrs"]])
    return stats.percentile(
        [(e["ts"] - begun[e["span"]]["ts"]) * 1e3 for e in record["spans"]
         if e["kind"] == "E" and e.get("span") in begun], q)
