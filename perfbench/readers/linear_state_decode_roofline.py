"""The lightning layers' one-token state update: its share of its roofline
over the traced slice.

Time: the summed device time of the ops named ``kernel`` + anything (the
Pallas kernel ``linear_state_decode`` of ``ops/linear_attention.py``). It
is bytes-bound: a row's state of one layer, ``heads * head_dim ** 2``
float32, is read once and written once a step, against ``4 * heads *
head_dim ** 2`` FLOPs. Rows and layers come from the ``event`` instants
stamped inside the slice (``rows``: the step's live rows; ``layers``: the
lightning layers). Padding rows, which the kernel points at one scratch
state, are not needed work.
"""

from .. import trace_reduce
from ..harness import log


def read(record, kernel, event):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not trace["planes"] or not peaks:
        return None
    seconds = trace_reduce.kernel_seconds(trace, [kernel])[0]
    if seconds <= 0:
        return None
    a, b = trace["t0"], trace["t0"] + trace["window_s"]
    updates = sum(e["attrs"]["rows"] * e["attrs"]["layers"]
                  for e in record.get("spans", ())
                  if e["name"] == event and a <= e["ts"] < b)
    if not updates:
        return None
    m = record["model"]
    state = m["lightning_nh"] * m["lightning_head_dim"] ** 2
    t_bytes = updates * 2 * state * 4 / peaks["hbm_bw_bytes"]
    t_flops = updates * 4 * state / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for {updates} "
        f"state updates of {state * 4 / 1e6:.2f} MB; bytes bound "
        f"{t_bytes * 1e3:.2f} ms, FLOPs bound {t_flops * 1e3:.3f} ms")
    return max(t_bytes, t_flops) / seconds * 100.0
