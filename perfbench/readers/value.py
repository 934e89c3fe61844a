"""A number the runner measured itself (``record["values"][key]``), scaled;
for a list of samples, its ``q``-th percentile."""

from .. import stats


def read(record, key, scale=1.0, q=None):
    v = record["values"].get(key)
    if isinstance(v, list):
        v = stats.percentile(v, 50 if q is None else q)
    return None if v is None else v * scale
