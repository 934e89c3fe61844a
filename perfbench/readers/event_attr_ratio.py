"""The sum of one attribute over the sum of another, over the program's
events of one name stamped inside the window, times ``scale``."""


def read(record, event, num, den, scale=1.0):
    w0, w1 = record["window"]
    found = [e["attrs"] for e in record.get("spans", ())
             if e["name"] == event and e["kind"] != "E"
             and w0 <= e["ts"] < w1 and num in e["attrs"]
             and den in e["attrs"]]
    total = sum(a[den] for a in found)
    return sum(a[num] for a in found) / total * scale if total else None
