"""The Gated DeltaNet layers' one-token update: its share of its roofline
over the traced slice.

Time: the summed device time of the ops named ``kernel`` + anything (the
Pallas kernels ``gated_delta_decode`` and ``gated_delta_decode_conv`` of
``ops/linear_attention.py``). It is bytes-bound: a row's delta state of one
layer, ``value_heads * key_dim * value_dim`` float32, and the convolution's
tail, ``(taps - 1) * channels`` float32, are each read once and written once
a step; the state's update is ``7 * value_heads * key_dim * value_dim`` FLOPs
(the decay, ``S^T k``, the rank-one write, ``S^T q``) and the convolution's
``2 * taps * channels``. Rows and layers come from the ``event`` instants
stamped inside the slice (``rows``: the step's live rows; ``layers``: the
Gated DeltaNet layers). Padding rows, which the kernels point at one scratch
row, are not needed work.
"""

from .. import trace_reduce
from ..harness import log


def need(model) -> tuple:
    """(bytes, FLOPs) one live row and layer needs a step."""
    state = model["linear_num_value_heads"] * model["linear_key_head_dim"] \
        * model["linear_value_head_dim"]
    channels = 2 * model["linear_num_key_heads"] \
        * model["linear_key_head_dim"] + model["linear_num_value_heads"] \
        * model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    return 2 * 4 * (state + (taps - 1) * channels), \
        7 * state + 2 * taps * channels


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
    nbytes, flops = need(record["model"])
    t_bytes = updates * nbytes / peaks["hbm_bw_bytes"]
    t_flops = updates * flops / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for {updates} "
        f"state updates of {nbytes / 1e6:.2f} MB; bytes bound "
        f"{t_bytes * 1e3:.2f} ms, FLOPs bound {t_flops * 1e3:.3f} ms")
    return max(t_bytes, t_flops) / seconds * 100.0
