"""The ``q``-th percentile of one attribute over the program's events of
one name stamped inside the window (instants such as
``serving.http.token``, or span begins)."""

from .. import stats


def read(record, event, attr, q):
    w0, w1 = record["window"]
    return stats.percentile(
        [e["attrs"][attr] for e in record["spans"]
         if e["name"] == event and e["kind"] != "E"
         and w0 <= e["ts"] < w1 and attr in e["attrs"]], q)
