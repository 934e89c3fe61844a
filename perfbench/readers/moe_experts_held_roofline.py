"""The routed experts' grouped matmuls where an expert's width is the file's
``moe_intermediate_size``: their share of their roofline over the traced
slice.

As ``moe_experts_roofline`` (the summed device time of the ops named
``kernel`` + anything against the larger of bytes / peak bytes/s and FLOPs /
peak FLOP/s; rows and expert touches from the ``events`` instants stamped
inside the slice), with the one thing that configuration's reader cannot
know: the width of a routed expert is ``moe_intermediate_size``
(``intermediate_size`` is here the width of a dense MLP no layer has).

* an expert touched in a layer and call reads its three matrices once:
  ``3 * hidden * width`` elements of the weight dtype;
* a row reads its input for the gate and the up matmul and writes both
  results, reads their product and writes the output: ``3 * hidden + 3 *
  width`` elements, counted in the weight dtype;
* a row does three matmuls: ``3 * 2 * hidden * width`` FLOPs.

Padding rows, pairs routed to experts held elsewhere and experts nobody
chose are not needed work.
"""

from .. import trace_reduce
from ..harness import log

_BYTES = {"bfloat16": 2, "float32": 4}


def need(model, rows: int, touched: int) -> tuple:
    """(bytes, FLOPs) for ``rows`` (token, expert) pairs on ``touched``
    (layer, expert) weight reads."""
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    size = _BYTES[model["serve"]["dtype"]]
    return (touched * 3 * hidden * width
            + rows * 3 * (hidden + width)) * size, rows * 6 * hidden * width


def read(record, kernel, events):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not trace["planes"] or not peaks:
        return None
    seconds = trace_reduce.kernel_seconds(trace, [kernel])[0]
    if seconds <= 0:
        return None
    a, b = trace["t0"], trace["t0"] + trace["window_s"]
    inside = [e["attrs"] for e in record.get("spans", ())
              if e["name"] in events and a <= e["ts"] < b]
    rows = sum(e["rows"] for e in inside)
    touched = sum(e["experts_touched"] for e in inside)
    if not rows or "moe_intermediate_size" not in record["model"]:
        return None
    nbytes, flops = need(record["model"], rows, touched)
    t_bytes = nbytes / peaks["hbm_bw_bytes"]
    t_flops = flops / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for {rows} rows on "
        f"{touched} expert touches; bytes bound {t_bytes * 1e3:.2f} ms, "
        f"FLOPs bound {t_flops * 1e3:.2f} ms -> "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'}-bound")
    return max(t_bytes, t_flops) / seconds * 100.0
