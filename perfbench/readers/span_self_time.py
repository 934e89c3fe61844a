"""Over the program's spans named ``span`` that began and ended inside the
window: the ``q``-th percentile of their self time in ms — the span's
duration less the part of it its child spans cover (the union of the
children's intervals, clipped to the span)."""

from .. import stats


def read(record, span, q=50):
    w0, w1 = record["window"]
    begins = {e["span"]: e for e in record["spans"] if e["kind"] == "B"}
    ends = {e["span"]: e["ts"] for e in record["spans"] if e["kind"] == "E"}
    kids = {}
    for sid, b in begins.items():
        if b.get("parent") in begins and sid in ends:
            kids.setdefault(b["parent"], []).append((b["ts"], ends[sid]))
    out = []
    for sid, b in begins.items():
        if b["name"] != span or sid not in ends or not w0 <= b["ts"] < w1:
            continue
        t0, t1 = b["ts"], ends[sid]
        covered, at = 0.0, t0
        for k0, k1 in sorted(kids.get(sid, ())):
            k0, k1 = max(k0, at), min(k1, t1)
            if k1 > k0:
                covered += k1 - k0
                at = k1
        out.append((t1 - t0 - covered) * 1e3)
    return stats.percentile(out, q)
