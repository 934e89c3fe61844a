"""The paged decode kernel's share of its roofline over the traced slice.

Kernel time: the summed device time of the ops named ``kernel`` + anything
(a Pallas kernel keeps its ``name`` in the trace). The least time the chip
could take is the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s,
both from shapes, for what the algorithm needs and no more:

* one decode row with ``t`` tokens of context reads K and V of those tokens
  once per layer: ``2 * kv_heads * head_dim * t`` elements of the KV dtype;
* and does ``q . K^T`` and ``p . V`` for every query head:
  ``2 * 2 * heads * head_dim * t`` FLOPs per layer.

Which rows ran with which context comes from the client's stamps: a token
with index ``i >= 1`` of a request arriving inside the slice was one decode
row with context ``prompt_len + i``. Padding rows of a batch bucket and the
page a context only partly fills are not needed work. With 32 query heads
on 8 KV heads in bf16 the bytes bound is 60 times the FLOPs bound: this
kernel is bandwidth-bound, and the log line says so.
"""

from .. import trace_reduce
from ..harness import log

_BYTES = {"bfloat16": 2, "bf16": 2, "native": 2, "int8": 1, "float32": 4}


def read(record, kernel):
    trace, peaks = record["trace"], record["peaks"]
    if not trace or not trace["planes"] or not peaks:
        return None
    seconds = trace_reduce.kernel_seconds(trace, [kernel])[0]
    if seconds <= 0:
        return None
    m = record["model"]
    head_dim = m["hidden_size"] // m["num_attention_heads"]
    a, b = trace["t0"], trace["t0"] + trace["window_s"]
    context = sum(r["prompt_len"] + i
                  for r in record["requests"]
                  for i, t in enumerate(r["tokens"]) if i and a <= t < b)
    layers = m["num_hidden_layers"]
    nbytes = (2 * m["num_key_value_heads"] * head_dim * context * layers
              * _BYTES[m["serve"]["kv_dtype"]])
    flops = 4 * m["num_attention_heads"] * head_dim * context * layers
    t_bytes = nbytes / peaks["hbm_bw_bytes"]
    t_flops = flops / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for {context} "
        f"context tokens x {layers} layers; bytes bound {t_bytes * 1e3:.2f} "
        f"ms, FLOPs bound {t_flops * 1e3:.3f} ms -> "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'}-bound")
    return max(t_bytes, t_flops) / seconds * 100.0
