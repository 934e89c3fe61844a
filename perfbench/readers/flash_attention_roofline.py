"""The flash attention kernels' share of their roofline over the traced
slice of a training cell: forward and both backward kernels together.

Kernel time: per chip, the summed device time of the ops named by
``kernels`` (+ anything), averaged over the chips. Needed work per step and
layer, from shapes: causal attention touches half of the ``seq x seq``
square, and a forward-and-backward pass needs six matmuls over it (``q.K^T``
and ``p.V`` forward; ``dV``, ``dP``, ``dQ``, ``dK`` backward), each
``2 * seq * seq / 2 * head_dim`` FLOPs per head and sequence. The second
forward that recompute runs, and the ``q.K^T`` the backward kernels redo, are
in the kernel time and not in the needed work, so the share cannot pass 100%
for that reason. Bytes (q, k, v, o, their gradients and the log-sum-exp, once
each way) are counted too; at seq 4096 the FLOPs bound is far the larger.
The work is split evenly over the chips of a mesh (batch over dp, heads over
mp).
"""

from .. import trace_reduce
from ..harness import log


def read(record, kernels):
    trace, peaks = record["trace"], record["peaks"]
    steps = record["values"].get("slice_steps")
    if not trace or not trace["planes"] or not peaks or not steps:
        return None
    per_chip = trace_reduce.kernel_seconds(trace, kernels)
    seconds = sum(per_chip) / len(per_chip)
    if seconds <= 0:
        return None
    m, t = record["model"], record["traffic"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // heads
    seq, batch, layers = t["seq"], t["batch"], m["num_hidden_layers"]
    chips = len(per_chip)
    flops = 6 * (2 * seq * seq // 2 * d) * heads * batch * layers * steps
    elems = (2 * heads + 2 * kv) * seq * d * 2 * batch * layers * steps
    t_flops = flops / chips / peaks["peak_flops"]
    t_bytes = elems * 2 / chips / peaks["hbm_bw_bytes"]
    log(f"flash kernels: {seconds * 1e3:.1f} ms a chip over {steps} steps; "
        f"FLOPs bound {t_flops * 1e3:.1f} ms, bytes bound "
        f"{t_bytes * 1e3:.1f} ms -> "
        f"{'FLOPs' if t_flops >= t_bytes else 'bytes'}-bound")
    return max(t_flops, t_bytes) / seconds * 100.0
