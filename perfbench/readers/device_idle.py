"""One minus the union of device op intervals over the traced slice, in
percent. ``chip`` is ``mean`` (over the chips used) or ``busiest`` (the chip
that waits least — under a mesh the others wait for it)."""

from .. import trace_reduce


def read(record, chip="mean"):
    trace = record["trace"]
    if not trace or not trace["planes"]:
        return None
    busy = trace_reduce.busy_seconds(trace)
    b = max(busy) if chip == "busiest" else sum(busy) / len(busy)
    return (1.0 - b / trace["window_s"]) * 100.0
