"""Seconds of set-up under the program's spans named ``span``: the union of
the intervals of those that ENDED before the window began (a span may lie
inside another of its name — a kernel body traced inside a program's trace —
and is then counted once). A program that opens no such span reads
nothing."""


def read(record, span):
    w0 = record["window"][0]
    begins = {e["span"]: e["ts"] for e in record["spans"]
              if e["kind"] == "B" and e["name"] == span}
    if not begins:
        return None
    total, at = 0.0, float("-inf")
    for t0, t1 in sorted((begins[e["span"]], e["ts"])
                         for e in record["spans"]
                         if e["kind"] == "E" and e.get("span") in begins
                         and e["ts"] <= w0):
        t0 = max(t0, at)
        if t1 > t0:
            total += t1 - t0
            at = t1
    return total
