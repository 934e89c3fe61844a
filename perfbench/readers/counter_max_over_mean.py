"""Over the window's growth of one labelled program counter: the largest
series over the mean of them all (``serving.moe.rows_by_expert_total``: the
busiest held expert over the mean — 1.0 is a perfectly even load). Series
that did not grow count as zeros only if they exist at the window's end."""


def read(record, counter):
    start, end = record["counters"]["start"], record["counters"]["end"]
    series = end.get(counter)
    if not isinstance(series, dict) or not series:
        return None
    before = start.get(counter) or {}
    grown = [v - before.get(k, 0.0) for k, v in series.items()]
    total = sum(grown)
    return max(grown) / (total / len(grown)) if total > 0 else None
