"""The routed experts' grouped matmuls: their share of their roofline over
the traced slice.

Time: the summed device time of the ops named ``kernel`` + anything (XLA
names a ``jax.lax.ragged_dot`` ``ragged-dot...``; the reduced trace keeps an
op's instruction name and not the ``jax.named_scope`` it was traced under,
so the sort's gathers and the weighting around the three matmuls are not in
the time, as they are not in the need). The least the
chip could take is the larger of bytes / peak bytes/s and FLOPs / peak
FLOP/s for what the algorithm needs and no more, from the program's own
counts — the instants named in ``events`` that were stamped inside the
slice carry ``rows`` (the (token, expert) pairs computed here) and
``experts_touched`` (held experts with at least one row, summed over
layers); name the instants of the calls that run the kernel
(``serving.moe.decode`` and ``serving.moe.prefill``):

* an expert touched in a layer and step reads its three matrices once:
  ``3 * hidden * expert_width`` elements of the weight dtype;
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


def read(record, kernel, events):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not trace["planes"] or not peaks:
        return None
    seconds = trace_reduce.kernel_seconds(trace, [kernel])[0]
    if seconds <= 0:
        return None
    a, b = trace["t0"], trace["t0"] + trace["window_s"]
    inside = [e["attrs"] for e in record["spans"]
              if e["name"] in events and a <= e["ts"] < b]
    rows = sum(e["rows"] for e in inside)
    touched = sum(e["experts_touched"] for e in inside)
    if not rows:
        return None
    m = record["model"]
    hidden, width = m["hidden_size"], m["intermediate_size"]
    size = _BYTES[m["serve"]["dtype"]]
    nbytes = (touched * 3 * hidden * width
              + rows * 3 * (hidden + width)) * size
    flops = rows * 6 * hidden * width
    t_bytes = nbytes / peaks["hbm_bw_bytes"]
    t_flops = flops / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for {rows} rows on "
        f"{touched} expert touches; bytes bound {t_bytes * 1e3:.2f} ms, "
        f"FLOPs bound {t_flops * 1e3:.2f} ms -> "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'}-bound")
    return max(t_bytes, t_flops) / seconds * 100.0
