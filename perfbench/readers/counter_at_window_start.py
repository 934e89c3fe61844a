"""A program counter's value at the window's first instant
(``record["counters"]["start"]``, which ``harness.snapshot_counters`` takes
there): what set-up counted. A counter the program does not have reads
nothing."""


def read(record, name):
    v = record["counters"]["start"].get(name)
    return v if isinstance(v, (int, float)) else None
