"""Device idle time by what the host was doing, per decode step: the
milliseconds of the first chip's idle gaps in the traced slice that fall
under the step thread's spans named in ``spans`` (innermost span winning:
``host_trace.py``), divided by the ``serving.decode`` spans that thread
began in the slice.

With ``spans`` empty it reads the rest: the gaps' total less what every
``layer_metrics/*.json`` over this reader claims, so the readings of one run
sum to the total by construction, and a new ``host_gap_*`` file for a new
span takes its share out of ``unnamed`` without an edit here. Time under a
span nobody claims (``serving.decode`` itself between its phases, a slice
edge where a span began before the profiler did) is unnamed: above a tenth
of the sum, the spans have a hole."""

import glob
import json
import os

from .. import harness, host_trace


def claimed():
    """Every span name some ``host_gap`` metric file lists."""
    names = set()
    for path in glob.glob(os.path.join(harness.HERE, "layer_metrics",
                                       "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("reader") == "host_gap":
            names.update(spec["args"]["spans"])
    return names


def read(record, spans):
    split = host_trace.split_of(record)
    if split is None or not split["steps"]:
        return None
    if spans:
        mine = set(spans).__contains__
    else:
        others = claimed()

        def mine(name):
            return name not in others
    ns = sum(v for name, v in split["by_span"].items() if mine(name))
    return ns * 1e-6 / split["steps"]
