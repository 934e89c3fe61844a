"""One program counter's growth over the window as a share of its own
growth plus that of the counters in ``rest`` (a counter nothing has
incremented yet counts as 0), times ``scale``."""


def read(record, num, rest, scale=1.0):
    start, end = record["counters"]["start"], record["counters"]["end"]
    if num not in end:
        return None

    def grown(name):
        return end.get(name, 0) - start.get(name, 0)
    total = grown(num) + sum(grown(r) for r in rest)
    return grown(num) / total * scale if total else None
