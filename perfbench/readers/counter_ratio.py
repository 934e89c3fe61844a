"""The ratio of two program counters' growth over the window, times
``scale``."""


def read(record, num, den, scale=1.0):
    start, end = record["counters"]["start"], record["counters"]["end"]
    if num not in end or den not in end:
        return None
    d = end[den] - start.get(den, 0)
    return (end[num] - start.get(num, 0)) / d * scale if d else None
